"""The profiler around a slice of the window, in its own run
(`--trace 1`). The trace goes to a fixed directory inside the
checkout, emptied first; the newest xplane is reduced."""

import glob
import os
import shutil
import time


class Tracer:
    def __init__(self, directory):
        self.directory = directory
        self.t0 = self.t1 = None

    def start(self):
        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # device lines are what is read
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        self.t0 = time.perf_counter()

    def stop(self):
        import jax
        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()

    @property
    def active(self):
        return self.t0 is not None and self.t1 is None

    def xplane(self):
        found = sorted(glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb")),
            key=os.path.getmtime)
        return found[-1] if found else None

    def reduce(self):
        from . import reduce_trace
        path = self.xplane()
        if path is None or self.t1 is None:
            return None
        return reduce_trace.reduce_file(path, self.t1 - self.t0)
