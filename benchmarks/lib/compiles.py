"""Backend compiles, their seconds and persistent-cache hits, as
jax.monitoring reports them (chip_smoke.py's listener). A cache hit
still fires the compile-duration event — its seconds are then the
retrieval — so `programs` counts both."""


class Compiles:
    def __init__(self):
        import jax.monitoring as jmon
        self.n = self.hits = self.misses = 0
        self.seconds = 0.0
        jmon.register_event_duration_secs_listener(self._dur)
        jmon.register_event_listener(self._ev)

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs

    def _ev(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snap(self):
        return (self.n, self.seconds, self.hits, self.misses)

    def since(self, s0):
        return {"programs": self.n - s0[0],
                "compile_seconds": self.seconds - s0[1],
                "cache_hits": self.hits - s0[2],
                "cache_misses": self.misses - s0[3]}
