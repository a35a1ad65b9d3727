"""Open-loop serving of a language model through the program's
ServeEngine: requests are submitted when due, whatever the server is
doing, and every latency is timed from the moment the request was due.

One process, one thread: the generator submits between scheduler rounds,
so how late it ran (its lateness) is reported beside the results. Every
prompt length the schedule holds is prefilled once during set-up, so
nothing compiles in the window whatever bucketing the program uses.
"""

from __future__ import annotations

import dataclasses
import importlib
import tempfile
import time
from collections import deque
from typing import Dict, List

import numpy as np

from benchmarks.chip import common, traffic

TRACE_S = 6.0            # traced stretch, at the end of the window
DRAIN_LIMIT_S = 120.0    # a request not done this long after the window never comes
SAMPLE_TOKENS = 600      # served tokens the reference checks, at least
SAMPLE_MAX = 16          # requests the reference checks, at most
WARMUP_RID = 1 << 30


@dataclasses.dataclass
class _Req:
    due: float                    # absolute perf_counter time
    prompt: np.ndarray
    max_new: int
    times: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _Step:
    t0: float
    t1: float
    prefill_tokens: List[int]     # prompt lengths admitted this round
    decode_ctx: List[int]         # live context of each decoded row


class Driver:
    def __init__(self, cell: Dict, cfg: Dict, mix: Dict, seed: int,
                 seconds: float, devices, clock: common.CompileClock,
                 bench_dir: str = common.HERE):
        self.cell, self.cfg, self.mix = cell, cfg, mix
        self.seed, self.seconds = seed, float(seconds)
        self.devices, self.clock = devices, clock
        self.chips = len(devices)
        fam = cfg["family"]
        self.ref = importlib.import_module(f"benchmarks.chip.reference.{fam}")
        self.adapter = importlib.import_module(
            f"benchmarks.chip.adapters.{fam}")
        self.bench_dir = bench_dir
        self.checks = common.load_json(bench_dir, "checks",
                                       f"{cell['name']}.json")
        self.trace_planes = None
        self.gc = common.GcClock()

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        import jax
        common.use_program()
        from repro.models.registry import build_model
        from repro.serve.engine import ServeEngine
        dep = self.cfg["deployment"]
        mcfg = self.adapter.model_config(self.cfg)
        mesh, out_sh = None, None
        if self.chips > 1:
            from repro.dist.sharding import serve_specs
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((self.chips,), ("model",), devices=self.devices)
            out_sh = serve_specs(mcfg, mesh, max_batch=dep["max_batch"],
                                 cache_len=dep["cache_len"],
                                 model=build_model(mcfg)).params
        cfg, ref, adapter = self.cfg, self.ref, self.adapter
        make = jax.jit(lambda kd: adapter.to_program(ref.all_weights(cfg, kd)),
                       out_shardings=out_sh)
        params = make(ref.key_data(self.seed))
        jax.block_until_ready(params)
        self.engine = ServeEngine(mcfg, params, max_batch=dep["max_batch"],
                                  cache_len=dep["cache_len"], mesh=mesh)
        del params
        self.schedule = traffic.schedule(self.mix, self.seed, self.seconds,
                                         int(cfg["vocab_size"]))
        self._warm_up()

    def _warm_up(self, lens=None) -> None:
        """Prefill every prompt length of the schedule (or `lens`) once,
        one token each, and decode once, then start from an empty
        engine."""
        from repro.serve.engine import Request
        eng = self.engine
        eng.reset()
        lens = sorted(lens or {len(r.prompt) for r in self.schedule})
        for i, n in enumerate(lens):
            eng.submit(Request(rid=WARMUP_RID + i,
                               prompt=np.zeros(n, np.int32),
                               max_new_tokens=2 if i == 0 else 1))
        while not eng.idle:
            eng.step()
        eng.reset()

    # ------------------------------------------------------------ window
    def run_window(self, trace: bool) -> None:
        import jax
        from repro.serve.engine import Request
        eng = self.engine
        eng.reset()
        pending = deque(self.schedule)
        self.reqs: Dict[int, _Req] = {}
        inflight: Dict[int, _Req] = {}
        self.steps: List[_Step] = []
        self.lateness: List[float] = []
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
        span = None
        c0 = self.clock.count
        gc0 = (self.gc.count, self.gc.seconds)
        self.gc.longest = 0.0
        t0 = time.perf_counter()
        end = t0 + self.seconds
        tr0 = t0 + max(0.0, self.seconds - TRACE_S)
        self.t0, self.t_end = t0, end
        self.trace_span = None
        self.window_stats = None
        traced_state = 0                      # 0 before, 1 on, 2 done
        while True:
            now = time.perf_counter()
            if self.window_stats is None and now >= end:
                self.window_stats = eng.finalize()
                self.queue_at_end = eng.queue_depth
                self.window_compiles = self.clock.count - c0
            if trace and traced_state == 0 and now >= tr0:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                span = jax.profiler.TraceAnnotation("bench.traced_window")
                span.__enter__()
                self.trace_span = [time.perf_counter(), None]
                traced_state = 1
            elif traced_state == 1 and now >= end:
                # stopping writes the trace out, which stalls the host for
                # seconds: it happens once the window has closed
                self.trace_span[1] = time.perf_counter()
                span.__exit__(None, None, None)
                jax.profiler.stop_trace()
                traced_state = 2
            while pending and t0 + pending[0].due_s <= now:
                r = pending.popleft()
                due = t0 + r.due_s
                eng.submit(Request(rid=r.rid, prompt=r.prompt,
                                   max_new_tokens=r.max_new_tokens,
                                   temperature=r.temperature), t_enqueue=due)
                self.lateness.append(now - due)
                rec = _Req(due=due, prompt=r.prompt, max_new=r.max_new_tokens)
                self.reqs[r.rid] = inflight[r.rid] = rec
            if eng.idle:
                if not pending and now >= end and traced_state != 1:
                    break
                wake = [end] if self.window_stats is None else []
                if pending:
                    wake.append(t0 + pending[0].due_s)
                if trace and traced_state == 0:
                    wake.append(tr0)
                time.sleep(max(0.0, min(wake) - time.perf_counter()))
                continue
            if now > end + DRAIN_LIMIT_S:
                break
            ts = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.step"):
                rep = eng.step()
            t = time.perf_counter()
            admitted = set(rep.admitted)
            step = _Step(ts, t, [len(inflight[r].prompt) for r in rep.admitted],
                         [])
            outs = eng.outputs
            for rid in list(inflight):
                rec = inflight[rid]
                new = len(outs.get(rid, ())) - len(rec.times)
                if new > 0:
                    rec.times.extend([t] * new)
                    if new - (rid in admitted) == 1:
                        step.decode_ctx.append(len(rec.prompt)
                                               + len(rec.times) - 1)
                if len(rec.times) >= rec.max_new:
                    del inflight[rid]
            self.steps.append(step)
        self.t_drained = time.perf_counter()
        self.drain_compiles = self.clock.count - c0
        self.gc_window = (self.gc.count - gc0[0], self.gc.seconds - gc0[1],
                          self.gc.longest)
        if traced_state == 1:
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.trace_span[1] = time.perf_counter()
        if trace:
            from benchmarks.chip import trace_reduce
            self.trace_planes = trace_reduce.from_xspace(
                trace_reduce.find_xspace(trace_dir))
            import shutil
            shutil.rmtree(trace_dir, ignore_errors=True)

    # ----------------------------------------------------------- results
    @property
    def attempted(self) -> int:
        return len(self.schedule)

    def _done(self, rec: _Req) -> bool:
        return len(rec.times) >= rec.max_new

    @property
    def failed(self) -> int:
        return sum(not self._done(r) for r in self.reqs.values()) + (
            len(self.schedule) - len(self.reqs))

    def end_to_end(self) -> Dict[str, float]:
        ttft, itl, in_window = [], [], 0
        for rec in self.reqs.values():
            first = rec.times[0] if rec.times else self.t_drained
            ttft.append(first - rec.due)
            itl.extend(np.diff(rec.times).tolist())
            in_window += sum(1 for t in rec.times if t <= self.t_end)
        return {"ttft_p95_ms": common.percentile(ttft, 95) * 1e3,
                "itl_p95_ms": common.percentile(itl, 95) * 1e3,
                "output_tok_s": in_window / self.seconds}

    def notes(self) -> Dict[str, float]:
        """Facts printed on standard error beside the result."""
        e2e = self.end_to_end()
        ws = self.window_stats or {}
        slow = max(self.steps, key=lambda s: s.t1 - s.t0)
        return {"generator_lateness_p95_ms":
                common.percentile(self.lateness, 95) * 1e3,
                "longest_step_ms": (slow.t1 - slow.t0) * 1e3,
                "longest_step_at_s": slow.t0 - self.t0,
                "gc_collections_pause_s_longest_s": self.gc_window,
                "requests": len(self.reqs), "decode_steps": ws.get(
                    "decode_steps"), "drain_s": self.t_drained - self.t_end,
                "compiles_window_and_drain": self.drain_compiles, **e2e}

    def counters(self) -> Dict:
        """What the per-layer readers take from this run."""
        # waits of the requests admitted inside the window (admission =
        # due + wait); the traced run's export stalls the host after it
        stats = self.engine.request_stats
        waits = [stats[r].queue_wait_s for r, rec in self.reqs.items()
                 if r in stats and rec.due + stats[r].queue_wait_s <= self.t_end]
        traced = []
        if self.trace_span:
            a, b = self.trace_span
            traced = [s for s in self.steps if s.t0 >= a and s.t1 <= b]
        return {"kind": "serve", "queue_wait_s": waits,
                "window_stats": self.window_stats or {},
                "max_batch": self.cfg["deployment"]["max_batch"],
                "compiles_in_window": self.drain_compiles,
                "traced_steps": traced,
                "decode_program": "_decode_masked",
                "prefill_program": "_prefill_slot"}

    # --------------------------------------------------------- the check
    def _sample(self) -> List[int]:
        done = [rid for rid, r in self.reqs.items() if self._done(r)]
        if not done:
            return []
        longest = max(done, key=lambda r: (len(self.reqs[r].times), -r))
        rng = traffic.seed_stream(self.seed, 3)
        rest = [r for r in rng.permutation(sorted(done)).tolist()
                if r != longest]
        pick, n = [longest], len(self.reqs[longest].times)
        for r in rest:
            if n >= SAMPLE_TOKENS or len(pick) >= SAMPLE_MAX:
                break
            pick.append(r)
            n += len(self.reqs[r].times)
        return pick

    def verify(self, control: bool = False):
        """(correct, {name: [value, limit]}) from the reference run over a
        seeded sample of the finished requests; with control=True the
        control's widest gap at the same positions is added."""
        outs = {r: list(self.engine.outputs[r]) for r in self.reqs
                if self._done(self.reqs[r])}
        del self.engine
        common.free_device_memory()
        pick = self._sample()
        limit = self.checks["widest_logit_gap"]["limit"]
        checks = {"unfinished_requests": [self.failed, 0]}
        if not pick:
            checks["widest_logit_gap"] = [None, limit]
            return False, checks
        tokens, rows, cols, served = self.reference_inputs(pick, outs)
        gap, cgap = self.ref.served_gaps(self.cfg, self.seed, tokens, rows,
                                         cols, served, control=control)
        widest = float(np.max(gap))
        checks["widest_logit_gap"] = [widest, limit]
        if control:
            checks["control_widest_logit_gap"] = [float(np.max(cgap)), limit]
        checks["tokens_compared"] = [int(len(served)), None]
        return (self.failed == 0 and widest <= limit), checks

    def reference_inputs(self, pick, outs):
        """Prompts followed by their served tokens (right-padded to a
        multiple of 512), and the positions whose next token was served."""
        seqs = []
        rows, cols, served = [], [], []
        for i, rid in enumerate(pick):
            p = self.reqs[rid].prompt
            o = outs[rid]
            seqs.append(np.concatenate([p, np.asarray(o[:-1], np.int32)]))
            for j, tok in enumerate(o):
                rows.append(i)
                cols.append(len(p) - 1 + j)
                served.append(tok)
        s = -(-max(len(x) for x in seqs) // 512) * 512
        tokens = np.zeros((len(seqs), s), np.int32)
        for i, x in enumerate(seqs):
            tokens[i, :len(x)] = x
        return tokens, rows, cols, served
