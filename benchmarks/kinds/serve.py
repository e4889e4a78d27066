"""Kind `serve`: many independent callers send small solves to an
in-process `serve.Server` at its cold defaults, open loop, at the rate
the traffic mix fixes. Each request is timed from when it was due to
when its ticket was seen done; a seeded sample of the answers, the
largest of each operation in it, is held to the configuration's
residual tolerance, and every answer to its shape and dtype.

The pool of problems is the same multiset of sizes for every seed
(lib/gen.py); the seed shuffles it, draws the entries and orders the
arrival gaps and the order in which the pool is gone through.
"""

import statistics

import numpy as np

from benchmarks.lib import gen, loadgen, refcheck


def pick_sample(cfg, seed, ids, problem):
    """`check_sample` of `ids` drawn from the seed, plus the largest
    problem of each operation; `problem(i)` is (op, a, b)."""
    ids = list(ids)
    r = gen.rng(seed, "sample")
    k = min(cfg["check_sample"], len(ids))
    sample = set(int(i) for i in r.choice(ids, size=k, replace=False)) \
        if k else set()
    for op in cfg["ops"]:
        mine = [i for i in ids if problem(i)[0] == op]
        if mine:
            sample.add(max(mine, key=lambda i: problem(i)[1].shape[0]))
    return sorted(sample)


def grade(cfg, ids, problem, answer):
    """The HPL scaled residuals of `ids` by operation, f64 on the host;
    `answer(i)` is the X to grade."""
    out = {op: [] for op in cfg["ops"]}
    for i in ids:
        op, a, b = problem(i)
        res = refcheck.hpl_resid(a.astype(np.float64), answer(i),
                                 b.astype(np.float64), a.shape[0])
        out[op].append(res if np.isfinite(res) else float("inf"))
    return out


class Cell:
    def __init__(self, cfg, mix, seed):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        r = gen.rng(seed, "pool")
        sizes = gen.uniform_sizes(cfg["pool"], cfg["n_min"], cfg["n_max"])
        ops = cfg["ops"]
        # neighbours in the ascending list take different operations,
        # so each operation sees the whole size law
        pool = [(ops[j % len(ops)], n) for j, n in enumerate(sizes)]
        r.shuffle(pool)
        self.pool = []
        for op, n in pool:
            n = int(n)
            a = gen.spd_gram(r, n) if op == "posv" \
                else gen.general(r, n)
            self.pool.append((str(op), a, gen.rhs(r, n, cfg["nrhs"])))
        self.srv = None
        self.rec = None

    # -- set-up -----------------------------------------------------------

    def warm(self, programs=True):
        """One dispatch at every (operation, bucket, power-of-two
        count) the queue can form from the pool, through a foreground
        queue of the same defaults; then the server itself, once.
        `programs=False` (tools/seed_readings.py, from its second seed
        on) skips the dispatches: the process already holds them."""
        from slate_tpu import batch, serve
        if programs:
            self._warm_programs(batch)
        self.srv = serve.Server()
        op, a, b = self.pool[0]
        self.srv.submit(op, a, b).result(timeout=900)

    def _warm_programs(self, batch):
        groups = {}
        for p in self.pool:
            groups.setdefault((p[0], batch.bucket_for(p[1].shape[0])),
                              []).append(p)
        with batch.CoalescingQueue(background=False) as q:
            self.max_batch = q.max_batch
            for (op, _), probs in sorted(groups.items()):
                k = 1
                while k <= self.max_batch:
                    ts = [q.submit(op, *probs[j % len(probs)][1:])
                          for j in range(k)]
                    q.flush()
                    for t in ts:
                        t.result(timeout=900)
                    k *= 2

    # -- the window -------------------------------------------------------

    def offer(self, rate, seconds, tracer=None):
        """`rate * seconds` requests, open loop; returns the records."""
        from slate_tpu import serve
        count = max(int(round(rate * seconds)), 1)
        due = loadgen.schedule(gen.rng(self.seed, "arrivals"), count, rate)
        pool, srv = self.pool, self.srv
        # the pool is gone through again and again, each time in a new
        # order: one fixed order would repeat its own run of large
        # neighbours some twenty times a window, and the seed that drew
        # it would read 8% apart from the next (PERF.md, PR 24)
        r = gen.rng(self.seed, "order")
        order = self.order = np.concatenate(
            [r.permutation(len(pool))
             for _ in range(-(-count // len(pool)))])[:count]

        def send(i):
            op, a, b = pool[order[i]]
            return srv.submit(op, a, b)

        hooks = []
        if tracer is not None:
            hooks.append((max(float(due[-1]) - self.mix["trace_slice_s"],
                              0.0), tracer.start))
        loop = loadgen.OpenLoop(due, send, grace_s=self.mix["grace_s"],
                                hooks=hooks)
        window = loop.run()
        if tracer is not None and tracer.active:
            tracer.stop()
        lat, answers, failed = [], {}, 0
        for i in range(count):
            x = None
            if loop.finished[i] is not None:
                try:
                    x = loop.handles[i].result(timeout=1.0)
                except Exception as e:      # the ticket carried an error
                    loop.errors[i] = e
            b = pool[order[i]][2]
            if x is not None and (getattr(x, "shape", None) != b.shape
                                  or x.dtype != np.float32):
                loop.errors[i] = TypeError(
                    "answer %r %r for rhs %r" % (getattr(x, "shape", None),
                                                 getattr(x, "dtype", None),
                                                 b.shape))
                x = None
            if x is None:
                failed += 1
                lat.append(window)          # counts as the window's length
            else:
                lat.append(loop.finished[i] - due[i])
                answers[i] = x
        refused = sum(isinstance(e, serve.ServeRejected)
                      for e in loop.errors if e is not None)
        self.rec = {
            "attempted": count, "failed": failed, "refused": refused,
            "window_s": window, "rate_per_s": rate,
            "lat_s": lat, "answers": answers,
            "late_s": [s - d for s, d in zip(loop.sent, due)
                       if s is not None],
            "backlog_at_close": sum(1 for f in loop.finished
                                    if f is None or f > window),
            "longest_stall": loadgen.longest_stall(loop),
            "first_errors": [repr(e)[:200] for e in loop.errors
                             if e is not None][:3]}
        return self.rec

    def window(self, seconds, tracer):
        rec = self.offer(self.mix["rate_per_s"], seconds, tracer)
        return {k: v for k, v in rec.items() if k != "answers"}

    def end_to_end(self):
        ms = [1e3 * v for v in self.rec["lat_s"]]
        return {"serve_p50_ms": statistics.median(ms),
                "serve_p95_ms": loadgen.percentile(ms, 95)}

    def close(self):
        if self.srv is not None:
            self.srv.close()
            self.srv = None

    # -- the guarantee ----------------------------------------------------

    def check(self):
        rec, cfg, pool, order = self.rec, self.cfg, self.pool, self.order

        def problem(i):
            return pool[order[i]]

        sample = pick_sample(cfg, self.seed, sorted(rec["answers"]),
                             problem)
        res = grade(cfg, sample, problem, rec["answers"].__getitem__)
        tol = cfg["tolerance"]
        compared = [["scaled_residual_max." + op, max(res[op], default=0.0),
                     tol["scaled_residual_max." + op]] for op in sorted(res)]
        failed = rec["failed"] + sum(
            v > tol["scaled_residual_max." + op]
            for op in res for v in res[op])
        return {"attempted": rec["attempted"], "failed": failed,
                "correct": failed == 0 and bool(sample),
                "compared": compared, "sampled": len(sample)}


def setup(cfg, mix, seed):
    return Cell(cfg, mix, seed)
