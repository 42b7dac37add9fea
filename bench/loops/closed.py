"""A closed loop: one caller calls the traffic's entry over a pool of
``pool`` inputs made on the device from the seed, cycled, and reads each
result as the caller would (the traffic's ``result``) before the next
call.  A call ends when its result is read.

Names it resolves: the traffic's ``entry`` (``bench/entries/<entry>.py``,
whose ``build(loop)`` returns the call) and ``result``
(``bench/results/<result>.py``: ``finish`` reads a result, ``errors``
compares the kept ones with the reference).
"""
import gc
import math
import time

import jax
import numpy as np

from bench import data, manifest


def _annotate(name: str):
    return jax.profiler.TraceAnnotation(name)


class Loop:
    def __init__(self, spec: dict, seed: int, devices, shape=None):
        t = spec["traffic"]
        self.cfg, self.traffic = spec["config"], t
        self.graph = [(op, dict(kw)) for op, kw in t["graph"]]
        self.pad = t["pad_value"]
        self.shape = tuple(shape) if shape else data.shape_of(self.cfg)
        self.seed = int(seed)
        self.devices = list(devices)
        self.rng = np.random.default_rng([self.seed % (1 << 64), 0x636b])
        self.keys = [data.key_for(self.seed, j) for j in range(int(t["pool"]))]
        self.voxels = math.prod(self.shape)
        self.entry = manifest.module("entries", t["entry"])
        self.result = manifest.module("results", t["result"])

    def make_data(self):
        self.pool = [data.make_volume(self.cfg, self.shape, k,
                                      device=self.devices[0])
                     for k in self.keys]
        jax.block_until_ready(self.pool)
        self.call = self.entry.build(self)

    def warmup(self):
        self.result.finish(self.call(self.pool[0]))
        t = time.perf_counter()
        self.result.finish(self.call(self.pool[1 % len(self.pool)]))
        self.warm_call_s = time.perf_counter() - t

    def window(self, seconds: float) -> dict:
        """Calls until ``seconds`` have passed; keeps every answer, or
        three drawn from the seed and the last, as the result says."""
        self.kept, keep_all = [], self.result.KEEP_ALL
        est = max(1, int(0.7 * seconds / max(self.warm_call_s, 1e-3)))
        sample = set(int(i) for i in self.rng.choice(
            est, size=min(3, est), replace=False))
        calls, last = 0, None
        t0 = time.perf_counter()
        deadline = t0 + seconds
        with _annotate("bench/window"):
            while True:
                j = calls % len(self.pool)
                with _annotate("bench/call"):
                    out = self.call(self.pool[j])
                with _annotate("bench/fetch"):
                    res = self.result.finish(out)
                t = time.perf_counter()
                if keep_all or calls in sample:
                    self.kept.append((j, res))
                else:
                    last = (j, res)
                calls += 1
                del out, res
                if t >= deadline:
                    break
        if last is not None:
            self.kept.append(last)
        return {"calls": calls, "voxels": calls * self.voxels,
                "window_s": t - t0, "attempted": calls, "failed": 0}

    def release(self):
        for x in self.pool:
            x.delete()
        self.pool, self.call = [], None
        gc.collect()

    def check(self, control: bool = False) -> dict:
        """Each compared number beside its limit (the traffic's
        ``limits``)."""
        errs = self.result.errors(self, self.kept, control)
        return {k: (v, self.traffic["limits"][k]) for k, v in errs.items()}
