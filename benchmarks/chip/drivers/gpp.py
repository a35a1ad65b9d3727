"""A closed loop of GPP calls through the program's kernel dispatch, as a
sigma calculation makes them: one caller, each call sent when the last
has returned. The inputs (a few sets, cycled) are made on the device
during set-up and stay resident.

Set-up seeds the program's tune cache from `gpp_tune_seed.json`, so that
every run, on every side of a comparison, dispatches the same blocks. The
program's own cache hygiene drops the entry where the kernel's block space
no longer holds it, and the tuner then picks as it would for a user.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import time
from typing import Dict, List

import numpy as np

from benchmarks.chip import common, flops
from benchmarks.chip.reference import gpp as ref

TRACE_S = 6.0            # traced stretch, at the end of the window


class Driver:
    def __init__(self, cell: Dict, cfg: Dict, mix: Dict, seed: int,
                 seconds: float, devices, clock: common.CompileClock,
                 bench_dir: str = common.HERE):
        self.cell, self.cfg, self.mix = cell, cfg, mix
        self.seed, self.seconds = seed, float(seconds)
        self.devices, self.clock = devices, clock
        self.chips = len(devices)
        self.shape = flops.GppShape.of(cfg)
        self.bench_dir = bench_dir
        self.checks = common.load_json(bench_dir, "checks",
                                       f"{cell['name']}.json")
        self.trace_planes = None
        self.gc = common.GcClock()

    def setup(self) -> None:
        import jax
        common.use_program()
        self.tune_dir = tempfile.mkdtemp(prefix="bench_tune_")
        shutil.copy(os.path.join(self.bench_dir, "gpp_tune_seed.json"),
                    os.path.join(self.tune_dir, "kernel_tune.json"))
        os.environ["REPRO_TUNE_CACHE"] = self.tune_dir
        from repro.tune import cache_tools
        import warnings
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            cache_tools.prune_cache(self.tune_dir)
        self.tune_stale = [str(w.message) for w in caught]
        import repro
        self.dispatch = repro.dispatch
        s = self.shape
        self.inputs = [ref.make_inputs(ref.key_data(self.seed, i), s.nbands,
                                       s.ngpown, s.ncouls, s.nw)
                       for i in range(int(self.mix["input_sets"]))]
        for inp in self.inputs:
            jax.block_until_ready(self.dispatch("gpp", inp))

    def run_window(self, trace: bool) -> None:
        import jax
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
        self.outputs: List = []
        self.done: List[float] = []
        self.trace_span = None
        c0 = self.clock.count
        gc0 = (self.gc.count, self.gc.seconds)
        self.gc.longest = 0.0
        k = len(self.inputs)
        t0 = time.perf_counter()
        tr0 = t0 + max(0.0, self.seconds - TRACE_S)
        span = None
        i = 0
        while True:
            now = time.perf_counter()
            if now - t0 >= self.seconds:
                break
            if trace and span is None and now >= tr0:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                span = jax.profiler.TraceAnnotation("bench.traced_window")
                span.__enter__()
                self.trace_span = [time.perf_counter(), None]
            with jax.profiler.TraceAnnotation("bench.call"):
                out = jax.block_until_ready(
                    self.dispatch("gpp", self.inputs[i % k]))
            self.outputs.append(out)
            self.done.append(time.perf_counter())
            i += 1
        if span is not None:
            self.trace_span[1] = time.perf_counter()
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        self.t0 = t0
        self.window_s = self.done[-1] - t0
        self.gc_window = (self.gc.count - gc0[0], self.gc.seconds - gc0[1],
                          self.gc.longest)
        self.window_compiles = self.clock.count - c0
        if trace:
            from benchmarks.chip import trace_reduce
            self.trace_planes = trace_reduce.from_xspace(
                trace_reduce.find_xspace(trace_dir))
            shutil.rmtree(trace_dir, ignore_errors=True)

    @property
    def attempted(self) -> int:
        return len(self.outputs)

    failed = 0

    def end_to_end(self) -> Dict[str, float]:
        return {"gpp_tflops": len(self.outputs) * flops.gpp_flops(self.shape)
                / self.window_s / 1e12}

    def notes(self) -> Dict:
        calls = np.diff([self.t0] + self.done)
        return {"calls": len(self.outputs), "window_s": self.window_s,
                "call_s_min_median_max": (float(calls.min()),
                                          float(np.median(calls)),
                                          float(calls.max())),
                "gc_collections_pause_s_longest_s": self.gc_window,
                "compiles_in_window": self.window_compiles,
                "tune_cache_pruned": self.tune_stale,
                **self.end_to_end()}

    def counters(self) -> Dict:
        traced = 0
        if self.trace_span:
            a, b = self.trace_span
            starts = [self.t0] + self.done[:-1]
            traced = sum(1 for s, e in zip(starts, self.done)
                         if s >= a and e <= b)
        return {"kind": "gpp", "compiles_in_window": self.window_compiles,
                "calls": len(self.outputs),
                "traced_calls": traced, "shape": self.shape}

    def verify(self, control: bool = False):
        """Every call's two outputs against the reference for its input
        set; with control=True also the control (the reference in
        bfloat16) against the reference."""
        k = len(self.inputs)
        got = [(np.asarray(a), np.asarray(x)) for a, x in self.outputs]
        want = [tuple(np.asarray(v) for v in ref.gpp(inp))
                for inp in self.inputs]
        worst = 0.0
        for i, (a, x) in enumerate(got):
            wa, wx = want[i % k]
            worst = max(worst, ref.rel_err(a, wa), ref.rel_err(x, wx))
        limit = self.checks["max_rel_err"]["limit"]
        shutil.rmtree(self.tune_dir, ignore_errors=True)
        checks = {"max_rel_err": [worst, limit],
                  "calls_compared": [len(got), None]}
        if control:
            cw = 0.0
            for inp, (wa, wx) in zip(self.inputs, want):
                ca, cx = ref.gpp(inp, "bfloat16")
                cw = max(cw, ref.rel_err(ca, wa), ref.rel_err(cx, wx))
            checks["control_max_rel_err"] = [cw, limit]
        return worst <= limit, checks
