"""Slot-level continuous-batching serving engine.

The engine owns a fixed pool of `max_batch` slots over ONE static-shape
decode batch (static shapes => one compiled decode step, the TPU-friendly
design). Requests wait in a FIFO admission queue; whenever a slot's request
finishes, the slot is refilled from the queue by prefilling the new request
into that slot's cache lines (Model.prefill_into_slot), so new requests
join the mid-flight batch without retracing and without disturbing their
batch-mates. One long request therefore occupies one slot, not the whole
batch — the occupancy failure of the old wave loop (process max_batch
requests, wait for the slowest, repeat) is gone.

Per-slot state lives in _Slot (rid, tokens remaining, temperature, done
flag, timing); per-row device state lives in the cache, whose "pos" is a
(B,) vector so every slot decodes at its own offset (models/registry.py,
transformer.attn_block_decode).

Sampling is deterministic PER REQUEST: token i of request rid is drawn
with fold_in(fold_in(base_key, rid), i), so identical requests produce
identical samples regardless of slot placement, batch-mates, or admission
order — and finished slots advance no shared RNG state (they have none to
advance). Finished slots are masked: their pos is held so their cache rows
stop growing, and their (discarded) sample comes from a constant dummy
lane. Greedy (temperature=0) rows take argmax.

Per-request latency/throughput stats (queue wait, TTFT, decode steps,
tokens/s) and engine aggregates (total decode steps, slot occupancy) are
collected on every run — `run(..., collect_stats=True)` returns them, and
`last_stats` always holds the most recent run's aggregates (the
benchmarks/run.py --serve table reads those into the repro-bench
artifact).

The scheduler is exposed at two granularities. `run(requests)` drains a
whole workload. The stepwise surface — `reset()`, `submit(request)`,
`step()` (one admission pass + one batched decode step, returning a
StepReport), `evict_inflight()`, `finalize()` — lets an outer driver
interleave many engines and inject/remove work mid-flight; the
multi-replica DP router (repro.serve.router) is built on it, re-queuing a
dead replica's evicted requests onto survivors. Because sampling is
per-request (below), a re-queued request restarted from scratch on any
replica regenerates the exact token stream the dead replica would have
produced.

Sharded serving: pass `mesh=` to run the engine tensor-parallel over a
`repro.dist` mesh. Params and the per-slot K/V cache shard head-wise per
`dist.sharding.serve_specs` (TP for attention/FFN weights, replicated
scheduler state); prefill_into_slot and the decode step execute as
sharded jitted computations while the FIFO slot loop stays host-side and
device-count-agnostic. See docs/serving.md §Sharded serving.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.registry import Model, build_model

# right-padding shape buckets for slot prefill: one compiled
# prefill_into_slot per bucket instead of one per distinct prompt length.
# Exact-length families (see _bucket_len) skip bucketing: ssm/hybrid fold
# pads into their recurrent state, and MoE capacity dispatch would let
# pads shift the shape-derived expert capacity and claim slots.
PREFILL_BUCKETS = (8, 16, 32, 64, 128, 256, 512)


@dataclasses.dataclass
class Request:
    """One serving request: a token prompt plus generation knobs.

    rid must be unique per engine run — it keys the output dict AND the
    per-request deterministic sample stream (fold_in(base_key, rid)), so
    two requests with the same rid would draw identical randomness.
    temperature 0.0 means greedy argmax.

    Example::

        import numpy as np, repro
        r = repro.Request(rid=0, prompt=np.array([3, 1, 4]),
                          max_new_tokens=8, temperature=0.7)
    """
    rid: int
    prompt: np.ndarray            # (S,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0
    # extra single-row model inputs, e.g. {"vis": (1, n_vis, D)} for vlm or
    # {"frames": (1, enc_seq, D)} for encdec
    extra: Optional[Dict[str, Any]] = None


@dataclasses.dataclass
class RequestStats:
    """Per-request latency/throughput, wall-clock measured by the engine."""
    rid: int
    prompt_len: int
    new_tokens: int
    queue_wait_s: float           # enqueue -> admitted into a slot
    ttft_s: float                 # enqueue -> first token sampled
    decode_steps: int             # batched decode steps this request rode
    total_s: float                # enqueue -> finished
    tok_per_s: float              # new_tokens / (finish - admit)


@dataclasses.dataclass
class _Slot:
    rid: int
    temperature: float
    remaining: int                # new tokens still to generate
    n_gen: int                    # tokens generated so far (rng fold index)
    prompt_len: int
    t_enqueue: float
    t_admit: float
    t_first: float
    decode_steps: int = 0


@dataclasses.dataclass
class StepReport:
    """What one ServeEngine.step() round did — the router's per-tick feed.

    admitted:    rids prefilled into a slot this round (their first token
                 was sampled during admission)
    finished:    rids whose last token was produced this round (including
                 degenerate max_new_tokens<1 requests, which finish
                 without ever occupying a slot)
    decoded:     occupied rows in this round's batched decode step (0 when
                 the decode was skipped because nothing was occupied)
    queue_depth: requests still waiting after this round's admissions
    """
    admitted: List[int]
    finished: List[int]
    decoded: int
    queue_depth: int


def percentile(xs, q: float) -> float:
    """Percentile with numpy's default linear interpolation, defined as
    0.0 on an empty sample (a run where nothing qualified). n=1 and
    all-equal samples degenerate to that single value for every q —
    tests/test_serve_stats.py pins these edges."""
    if len(xs) == 0:
        return 0.0
    return float(np.percentile(np.asarray(xs, np.float64), q))


def request_tpot_s(st: "RequestStats") -> Optional[float]:
    """Time-per-output-token of one finished request: the decode time
    after its first token spread over the remaining tokens,
    (total_s - ttft_s) / (new_tokens - 1). Undefined (None) for requests
    with fewer than two tokens — a max_new_tokens<=1 request has no
    inter-token gap to measure, so it contributes no TPOT sample."""
    if st.new_tokens < 2:
        return None
    return (st.total_s - st.ttft_s) / (st.new_tokens - 1)


def aggregate_engine_stats(per_req: Dict[int, "RequestStats"], *,
                           n_requests: int, n_steps: int, n_prefills: int,
                           slot_steps_active: int, max_batch: int,
                           wall_s: float) -> Dict[str, Any]:
    """Fold per-request stats + scheduler counters into the engine dict
    (the `last_stats` schema benchmarks/run.py --serve reads).

    Definitions (tests/test_serve_stats.py pins these):
      occupancy   = slot_steps_active / (n_steps * max_batch); an idle run
                    (no decode steps) is vacuously fully occupied (1.0).
      tok_per_s   = total generated tokens / wall_s (engine throughput,
                    prefill + decode inclusive since wall_s spans the run).
      mean_*      = arithmetic means over finished requests (0.0 when no
                    request finished).
      p50/p99_*   = distribution tails (linear-interpolated percentiles).
                    TTFT samples come from requests that produced at least
                    one token (a max_new_tokens<1 request records a
                    vacuous 0.0 TTFT and is excluded); TPOT samples from
                    requests with >= 2 tokens (see request_tpot_s).
    """
    total_new = sum(st.new_tokens for st in per_req.values())
    ttfts = [st.ttft_s for st in per_req.values() if st.new_tokens > 0]
    tpots = [t for t in (request_tpot_s(st) for st in per_req.values())
             if t is not None]
    return {
        "p50_ttft_s": percentile(ttfts, 50),
        "p99_ttft_s": percentile(ttfts, 99),
        "p50_tpot_s": percentile(tpots, 50),
        "p99_tpot_s": percentile(tpots, 99),
        "requests": n_requests,
        "decode_steps": n_steps,
        "prefills": n_prefills,
        "new_tokens": total_new,
        "occupancy": (slot_steps_active / (n_steps * max_batch)
                      if n_steps else 1.0),
        "wall_s": wall_s,
        "tok_per_s": total_new / max(wall_s, 1e-9),
        "mean_queue_wait_s": (float(np.mean([s.queue_wait_s
                                             for s in per_req.values()]))
                              if per_req else 0.0),
        "mean_ttft_s": (float(np.mean([s.ttft_s
                                       for s in per_req.values()]))
                        if per_req else 0.0),
    }


class ServeEngine:
    """Slot-level continuous-batching LM server over one compiled decode
    step. See the module docstring for the scheduling model and
    docs/serving.md for the full guide.

    Example (tiny model, CPU)::

        import jax, numpy as np, repro
        from repro.configs.base import get_config, reduce_config
        cfg = reduce_config(get_config("qwen2-1.5b"), d_model=64, vocab=128)
        params = repro.build_model(cfg).init_params(jax.random.PRNGKey(0))
        eng = repro.ServeEngine(cfg, params, max_batch=2, cache_len=64)
        out = eng.run([repro.Request(rid=0, prompt=np.arange(5),
                                     max_new_tokens=8)])

    mesh: optional `jax.sharding.Mesh` with a "model" axis — the engine
    then serves tensor-parallel: params and the slot K/V cache shard per
    `repro.dist.sharding.serve_specs`, the scheduler stays host-side, and
    outputs are bit-exact vs the mesh-less engine on a 1-device mesh.
    """

    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 4,
                 cache_len: int = 512, rng_seed: int = 0, mesh=None,
                 kv_page_size: int = 0, kv_pages: Optional[int] = None,
                 kv_dtype: str = "bf16", prefix_reuse: bool = True,
                 draft_cfg: Optional[ModelConfig] = None, draft_params=None,
                 spec_k: int = 0):
        self.cfg = cfg
        self.model: Model = build_model(cfg)
        self.max_batch = max_batch
        self.cache_len = cache_len
        self.mesh = mesh
        # paged K/V cache (serve/kvcache.py): kv_page_size > 0 switches the
        # slot caches to page accounting + prefix reuse. The page pool is
        # host-managed and per-replica, so it is gated off the TP mesh path
        # (the sharded cache layout is pinned by serve_specs).
        if kv_page_size and mesh is not None:
            raise ValueError("paged K/V cache (kv_page_size>0) does not "
                             "compose with mesh= tensor parallelism")
        self.kv_page_size = kv_page_size
        self.kv_pages = kv_pages
        self.kv_dtype = kv_dtype
        self.prefix_reuse = prefix_reuse
        self._kv = None
        # speculative decoding (serve/spec.py): spec_k > 0 pairs the target
        # with a small draft model that proposes spec_k candidates per
        # active slot each round, verified by ONE (spec_k+1)-position
        # target forward (Model.decode_verify). The accept loop and the
        # per-row position rollback are host-managed like the page pool,
        # so spec is gated off the TP mesh path too.
        self.spec_k = spec_k
        self.draft_cfg = draft_cfg
        self.draft_params = draft_params
        self.draft_model: Optional[Model] = None
        self._draft_cache = None
        self._spec_inflight: Dict[int, int] = {}
        self._spec: Optional[Dict[str, int]] = None
        if spec_k:
            if mesh is not None:
                raise ValueError("speculative decoding (spec_k>0) does not "
                                 "compose with mesh= tensor parallelism")
            if draft_cfg is None or draft_params is None:
                raise ValueError("spec_k>0 requires draft_cfg= and "
                                 "draft_params=")
            if draft_cfg.family != "dense":
                raise ValueError("draft model must be a dense decoder "
                                 f"(per-row K/V rollback): {draft_cfg.family!r}")
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    "draft/target vocab mismatch: "
                    f"{draft_cfg.vocab_size} vs {cfg.vocab_size}")
            self.draft_model = build_model(draft_cfg)
        # never split: per-request sample keys are fold_in derivations of
        # this base, so no shared RNG state advances across requests.
        self.rng = jax.random.PRNGKey(rng_seed)
        self.last_stats: Optional[Dict[str, Any]] = None
        # scheduler state is armed lazily: reset() allocates the cache, so
        # constructing an engine stays cheap; run() resets every time and
        # submit() resets on first use. queue/slots exist from birth so
        # idle/queue_depth/active_count are safe to read before the first
        # reset.
        self._cache = None
        self._queue: deque = deque()
        self._slots: List[Optional[_Slot]] = [None] * max_batch

        if mesh is not None:
            from repro.dist.sharding import serve_specs
            from repro.models.layers import DistCtx
            self._specs = serve_specs(cfg, mesh, max_batch=max_batch,
                                      cache_len=cache_len, model=self.model)
            # commit params onto the mesh once; every jitted step below
            # inherits the layout (row/column-parallel weights, head-wise
            # sharded cache, replicated scheduler state)
            self.params = jax.device_put(params, self._specs.params)
            # the ctx threads per-shard sizes into the flash/ssm registry
            # dispatch (tuned block configs key on the LOCAL shard of the
            # problem, not the global shape)
            self._ctx = DistCtx(mesh=mesh, data_axes=(), model_axis="model")
            self._cache_bytes_local = self._local_cache_bytes()
            # the cache is made where it lives: each device zeroes only its
            # own shards. Made whole on one device and then split, it must
            # fit there beside that device's weights, and codeqwen1.5-7b's
            # (4.3 GB over four chips) does not.
            self._zero_cache_sharded = jax.jit(
                self._zero_cache, out_shardings=self._specs.cache)
        else:
            self._specs = None
            self._ctx = None
            self.params = params
            self._cache_bytes_local = 0

        # the block of the live-line decode kernel where the dense stacks'
        # per-row decode runs it (transformer.reads_live_lines); 0 where
        # each decode step reads every line of every slot
        from repro.models.transformer import reads_live_lines
        self._kv_blk = 0
        if cfg.family in ("dense", "moe", "vlm") and \
                reads_live_lines(True, 0, False):
            from repro.kernels.live_decode.kernel_def import block_for
            self._kv_blk = block_for(cache_len)

        from repro.models.layers import exact_tp_scope

        # the jitted functions' names are the compiled modules' names
        # (jit__decode_masked, jit__prefill_slot), which a profile reads;
        # the named scopes tag every device op they hold
        def _decode_masked(p, c, t, active):
            # exact_tp_scope is trace-time: with a mesh it makes every
            # row-parallel contraction gather-then-compute (bit-exact);
            # mesh=None makes it a no-op.
            with jax.named_scope("serve.decode"):
                with exact_tp_scope(mesh):
                    logits, new = self.model.decode_step(p, c, t, self._ctx,
                                                         active=active)
                # done-row masking: hold finished slots' pos so their
                # cache rows stop growing — the step writes one (masked,
                # invisible) line at the held position and the row costs
                # nothing semantically.
                new["pos"] = jnp.where(active, new["pos"], c["pos"])
            return logits, new

        def _prefill_slot(p, c, s, b, n):
            with jax.named_scope("serve.prefill"), exact_tp_scope(mesh):
                return self.model.prefill_into_slot(p, c, s, b, n,
                                                    self._ctx)

        # each program that takes the cache (argument 1) and returns it is
        # donated the cache: it writes its new lines in place and deletes
        # the arrays it was handed, and the engine rebinds its cache to
        # the result.
        if mesh is None:
            self._decode = jax.jit(_decode_masked, donate_argnums=1)
            self._prefill_slot = jax.jit(_prefill_slot, donate_argnums=1)
        else:
            # pin the cache layout across steps (out_shardings) so XLA
            # cannot silently gather the sharded K/V between prefill and
            # decode; logits replicate — the host samples from them.
            sp = self._specs
            self._decode = jax.jit(
                _decode_masked,
                in_shardings=(sp.params, sp.cache, sp.replicated,
                              sp.replicated),
                out_shardings=(sp.replicated, sp.cache), donate_argnums=1)
            self._prefill_slot = jax.jit(
                _prefill_slot,
                in_shardings=(sp.params, sp.cache, sp.replicated,
                              sp.replicated, sp.replicated),
                out_shardings=(sp.replicated, sp.cache), donate_argnums=1)
        self._sample = jax.jit(self._sample_batch_impl)

        # suffix prefill for prefix-reuse admissions (dense family only —
        # Model.prefill_continue is None elsewhere and hits never occur)
        self._prefill_cont = None
        if kv_page_size and self.model.prefill_continue is not None:
            def _prefill_cont(p, c, s, b, st, n):
                with jax.named_scope("serve.prefill"), exact_tp_scope(mesh):
                    return self.model.prefill_continue(p, c, s, b, st, n,
                                                       self._ctx)
            self._prefill_cont = jax.jit(_prefill_cont, donate_argnums=1)

        if spec_k:
            if self.model.decode_verify is None:
                raise ValueError(
                    "target family has no multi-position decode_verify "
                    f"entry (spec_k>0 needs one): {cfg.family!r}")

            def _draft_decode(p, c, t, active):
                logits, new = self.draft_model.decode_step(p, c, t, None,
                                                           active=active)
                new["pos"] = jnp.where(active, new["pos"], c["pos"])
                return logits, new

            def _draft_prefill(p, c, s, b, n):
                return self.draft_model.prefill_into_slot(p, c, s, b, n,
                                                          None)

            def _verify_masked(p, c, t, active):
                # the (spec_k+1)-position verify forward; done-row masking
                # holds finished slots' pos exactly like _decode_masked
                logits, new = self.model.decode_verify(p, c, t, self._ctx)
                new["pos"] = jnp.where(active, new["pos"], c["pos"])
                return logits, new

            self._draft_decode = jax.jit(_draft_decode, donate_argnums=1)
            self._draft_prefill = jax.jit(_draft_prefill,
                                          donate_argnums=1)
            self._verify = jax.jit(_verify_masked, donate_argnums=1)
            self._spec_sample = jax.jit(self._spec_sample_impl)

    # ------------------------------------------------------------- sampling

    @staticmethod
    def _sample_batch_impl(logits: jax.Array, temps: jax.Array,
                           base_key: jax.Array, rids: jax.Array,
                           ngens: jax.Array) -> jax.Array:
        """Whole-batch next-token sampler, one compiled call per step.
        Greedy rows take argmax; temperature rows draw categorically with a
        per-request key fold_in(fold_in(base, rid), token_index) — no row's
        draw depends on its batch-mates or on any mutable RNG state."""
        def draw(rid, ngen, row, temp):
            key = jax.random.fold_in(jax.random.fold_in(base_key, rid), ngen)
            return jax.random.categorical(key, row / jnp.maximum(temp, 1e-6))

        with jax.named_scope("serve.sample"):
            lg = logits.astype(jnp.float32).reshape(logits.shape[0], -1)
            greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            sampled = jax.vmap(draw)(rids, ngens, lg, temps).astype(jnp.int32)
            return jnp.where(temps > 0, sampled, greedy)

    @staticmethod
    def _spec_sample_impl(logits: jax.Array, temps: jax.Array,
                          base_key: jax.Array, rids: jax.Array,
                          ngens: jax.Array, salt: jax.Array) -> jax.Array:
        """Draft-proposal sampler for spec rounds. Greedy rows take the
        draft argmax; temperature rows draw from the DRAFT distribution
        with the salted per-request key (serve/spec.py key schedule), so
        spec-round draws can never collide with the plain path's un-salted
        sample stream or with the accept/residual/bonus draws."""
        lg = logits.astype(jnp.float32).reshape(logits.shape[0], -1)
        greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)

        def draw(rid, ngen, row, temp):
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.fold_in(base_key, rid), ngen),
                salt)
            return jax.random.categorical(key, row / jnp.maximum(temp, 1e-6))

        sampled = jax.vmap(draw)(rids, ngens, lg, temps).astype(jnp.int32)
        return jnp.where(temps > 0, sampled, greedy)

    def _sample_rows(self, logits, slots: List[Optional[_Slot]]) -> np.ndarray:
        """Sample every row and pull the tokens to the host, a
        device-to-host round trip (span `serve.sample`)."""
        with jax.profiler.TraceAnnotation(
                "serve.sample", rows=sum(s is not None for s in slots)):
            temps = np.array([s.temperature if s else 0.0 for s in slots],
                             np.float32)
            rids = np.array([s.rid if s else -1 for s in slots], np.int32)
            ngens = np.array([s.n_gen if s else 0 for s in slots], np.int32)
            return np.asarray(self._sample(
                logits, jnp.asarray(temps), self.rng, jnp.asarray(rids),
                jnp.asarray(ngens)))

    # ------------------------------------------------------------ admission

    def _bucket_len(self, n: int, room: int) -> int:
        # exact-length families: recurrent state (ssm/hybrid) folds every
        # token in, and MoE capacity dispatch is token-count sensitive
        # (pad tokens would shift the shape-derived expert capacity and
        # compete for slots) — for them one trace per prompt length is the
        # price of correctness. Pure-attention stacks are causal, so right
        # pads are invisible to real tokens and bucketing is free. `room`
        # caps the padded length so the row's cache lines (including any
        # prepended vis tokens) still fit the slot.
        if self.cfg.family in ("ssm", "hybrid", "moe"):
            return n
        for b in PREFILL_BUCKETS:
            if n <= b <= room:
                return b
        return n

    def _zero_cache(self):
        cache = self.model.init_cache(self.max_batch, self.cache_len)
        # per-row positions: each slot decodes at its own offset
        cache["pos"] = jnp.zeros((self.max_batch,), jnp.int32)
        return cache

    def _fresh_cache(self):
        if self._specs is None:
            return self._zero_cache()
        return self._zero_cache_sharded()

    # ------------------------------------------------------------ per-device

    def _local_cache_bytes(self) -> int:
        """One device's cache shard bytes, from the pinned shard shapes
        (identical per device: shard_shape is uniform). The layout is
        fixed at construction, so this is computed once in __init__."""
        ab = self.model.init_cache(self.max_batch, self.cache_len,
                                   abstract=True)
        ab["pos"] = jax.ShapeDtypeStruct((self.max_batch,), jnp.int32)
        cache_b = 0
        for sharding, leaf in zip(jax.tree.leaves(self._specs.cache),
                                  jax.tree.leaves(ab)):
            n = 1
            for s in sharding.shard_shape(leaf.shape):
                n *= s
            cache_b += n * leaf.dtype.itemsize
        return cache_b

    def device_stats(self) -> List[Dict[str, Any]]:
        """Per-device shard accounting on a sharded engine ([] without a
        mesh): for every mesh device, the bytes of its local param shards
        (measured from the committed arrays) and of its local cache
        shards. benchmarks/run.py --serve --mesh emits one artifact row
        per entry."""
        if self.mesh is None:
            return []
        params_b: Dict[int, int] = {d.id: 0 for d in self.mesh.devices.flat}
        for leaf in jax.tree.leaves(self.params):
            for sh in leaf.addressable_shards:
                params_b[sh.device.id] += sh.data.nbytes
        return [{"device": did, "params_bytes": pb,
                 "cache_bytes": self._cache_bytes_local}
                for did, pb in sorted(params_b.items())]

    def _admit(self, cache, slot_idx: int, r: Request, t_enqueue: float):
        """Prefill r into slot_idx's cache lines; returns
        (new cache, slot state, first sampled token). The span
        `serve.admit` covers the admission up to the first token on the
        host; its args tie it to the request and its prefill bucket."""
        with jax.profiler.TraceAnnotation("serve.admit", rid=r.rid,
                                          prompt_len=len(r.prompt)) as span:
            plen = len(r.prompt)
            if self.cfg.family == "vlm":
                plen += self.cfg.n_vis_tokens  # vis tokens take cache lines
            # spec rounds write up to spec_k speculative lines past the
            # committed region before the accept decision rolls pos back,
            # so a spec engine reserves that headroom in every slot
            assert plen + r.max_new_tokens + self.spec_k <= self.cache_len, (
                f"request {r.rid}: prompt {plen} + max_new "
                f"{r.max_new_tokens} + spec_k {self.spec_k} exceeds "
                f"cache_len {self.cache_len}")
            vis = plen - len(r.prompt)
            # paged cache: open the block table (allocating pages for the
            # prompt) and consult the prefix index. A hit restores the
            # cached K/V pages into the slot row and prefills only the
            # unseen suffix.
            hit = None
            if self._kv is not None:
                hit = self._kv.admit(r.rid, np.asarray(r.prompt, np.int32),
                                     plen, r.max_new_tokens)
            t_admit = time.perf_counter()
            if hit is not None:
                cache = self._kv.restore_prefix(cache, slot_idx, hit)
                start = hit.tokens
                suffix = np.asarray(r.prompt[start:], np.int32)
                real = len(suffix)
                padded = self._bucket_len(real, self.cache_len - start)
                toks = np.zeros((1, padded), np.int32)
                toks[0, :real] = suffix
                with jax.profiler.TraceAnnotation("serve.prefill"):
                    logits, cache = self._prefill_cont(
                        self.params, cache, np.int32(slot_idx),
                        {"tokens": jnp.asarray(toks)}, np.int32(start),
                        np.int32(real))
            else:
                real = len(r.prompt)
                padded = self._bucket_len(real, self.cache_len - vis)
                toks = np.zeros((1, padded), np.int32)
                toks[0, :real] = r.prompt  # right pad: masked by pos
                batch = {"tokens": jnp.asarray(toks)}
                if r.extra:
                    batch.update(r.extra)
                with jax.profiler.TraceAnnotation("serve.prefill"):
                    logits, cache = self._prefill_slot(
                        self.params, cache, np.int32(slot_idx), batch,
                        np.int32(plen))
            span.set_metadata(bucket=padded)
            # prompt tokens prefilled, and the padded bucket's tokens
            # that prefill paid for (finalize() reports both)
            self._n_prefill_tokens += real
            self._n_prefill_bucket_tokens += padded
            if self._kv is not None and self._kv.prefix_reuse:
                # publish this prompt's full pages for future admissions
                cache = self._kv.insert_prefix(
                    np.asarray(r.prompt, np.int32), r.rid, cache, slot_idx)
            if self.spec_k:
                # the draft keeps its own slot-resident K/V lines (never
                # page-accounted: the page pool tracks committed TARGET
                # lines only) and always prefills the full prompt — it has
                # no prefix store, and its first-token logits are discarded
                # (the first token comes from the target, the bit-exactness
                # contract)
                dpad = self._bucket_len(len(r.prompt), self.cache_len)
                dtoks = np.zeros((1, dpad), np.int32)
                dtoks[0, : len(r.prompt)] = r.prompt
                _, self._draft_cache = self._draft_prefill(
                    self.draft_params, self._draft_cache,
                    np.int32(slot_idx), {"tokens": jnp.asarray(dtoks)},
                    np.int32(plen))
            slot = _Slot(rid=r.rid, temperature=r.temperature,
                         remaining=r.max_new_tokens, n_gen=0,
                         prompt_len=plen, t_enqueue=t_enqueue,
                         t_admit=t_admit, t_first=0.0)
            first = int(self._sample_rows(logits, [slot])[0])
            slot.t_first = time.perf_counter()
            slot.n_gen = 1
            slot.remaining -= 1
            return cache, slot, first

    # ------------------------------------------------------------ scheduler
    #
    # The scheduler is incremental: reset() arms a fresh run, submit()
    # enqueues requests at any point, and step() performs one scheduler
    # round (admit free slots FIFO, then one batched decode step). run()
    # is the drain-everything convenience built on top; a multi-replica
    # router (repro.serve.router) instead interleaves submit()/step()
    # across engines and uses evict_inflight() for failover re-queue.

    def reset(self) -> None:
        """Arm a fresh scheduling run: empty queue/slots, a fresh cache,
        zeroed counters. Called by run(); a stepwise driver (the router)
        calls it once before its first submit()."""
        self._queue: deque = deque()
        self._reqs: Dict[int, Request] = {}      # in-flight rid -> Request
        self._t_enq: Dict[int, float] = {}
        self._out: Dict[int, List[int]] = {}
        self._per_req: Dict[int, RequestStats] = {}
        self._slots: List[Optional[_Slot]] = [None] * self.max_batch
        # the last run's device state goes before the next is allocated:
        # two caches never live at once
        self._cache = self._draft_cache = self._kv = None
        self._cache = self._fresh_cache()
        if self.kv_page_size:
            from repro.serve.kvcache import PagedKVCache
            self._kv = PagedKVCache(
                self.cfg, max_batch=self.max_batch,
                cache_len=self.cache_len, page_size=self.kv_page_size,
                n_pages=self.kv_pages, kv_dtype=self.kv_dtype,
                prefix_reuse=self.prefix_reuse)
        if self.spec_k:
            dc = self.draft_model.init_cache(self.max_batch, self.cache_len)
            dc["pos"] = jnp.zeros((self.max_batch,), jnp.int32)
            self._draft_cache = dc
        self._spec_inflight = {}
        self._spec = {"proposed": 0, "accepted": 0, "rejected": 0,
                      "bonus": 0, "tokens_emitted": 0, "verify_steps": 0,
                      "draft_steps": 0}
        self._cur = np.zeros((self.max_batch, 1), np.int32)
        self._n_steps = 0          # global batched decode steps
        self._n_prefills = 0
        self._n_prefill_tokens = 0
        self._n_prefill_bucket_tokens = 0
        self._n_submitted = 0
        self._slot_steps_active = 0
        self._kv_lines_read = 0
        self._kv_lines_full = 0
        self._t_start = time.perf_counter()

    @property
    def idle(self) -> bool:
        """True when nothing is queued and every slot is free."""
        return not self._queue and all(s is None for s in self._slots)

    @property
    def kv(self):
        """The run's PagedKVCache (None until reset() on a paged engine,
        always None with kv_page_size=0). Chaos tests call its
        check_conservation() through every evict/fence/recover path."""
        return self._kv

    @property
    def queue_depth(self) -> int:
        """Requests admitted by submit() but not yet occupying a slot."""
        return len(self._queue)

    @property
    def active_count(self) -> int:
        """Slots currently decoding a request."""
        return sum(1 for s in self._slots if s is not None)

    @property
    def outputs(self) -> Dict[int, List[int]]:
        """Tokens generated so far this run, {rid: [tok, ...]}."""
        return self._out

    @property
    def request_stats(self) -> Dict[int, RequestStats]:
        """Per-request records of requests FINISHED so far this run."""
        return self._per_req

    def submit(self, r: Request, *, t_enqueue: Optional[float] = None
               ) -> None:
        """Enqueue one request (FIFO). t_enqueue backdates the queue-wait/
        TTFT clock — a router passes the moment the request arrived at the
        router, so latency spans its whole queueing life, including a
        failed first attempt on a replica that died."""
        if self._cache is None:
            self.reset()
        self._queue.append(r)
        self._reqs[r.rid] = r
        self._t_enq[r.rid] = (time.perf_counter() if t_enqueue is None
                              else t_enqueue)
        self._out[r.rid] = []
        self._n_submitted += 1

    def _finish(self, i: int) -> int:
        s = self._slots[i]
        now = time.perf_counter()
        self._per_req[s.rid] = RequestStats(
            rid=s.rid, prompt_len=s.prompt_len, new_tokens=s.n_gen,
            queue_wait_s=s.t_admit - s.t_enqueue,
            ttft_s=s.t_first - s.t_enqueue,
            decode_steps=s.decode_steps, total_s=now - s.t_enqueue,
            tok_per_s=s.n_gen / max(now - s.t_admit, 1e-9))
        self._slots[i] = None
        self._reqs.pop(s.rid, None)
        if self._kv is not None:
            self._kv.release(s.rid)     # terminal outcome: free pages once
        return s.rid

    def step(self) -> StepReport:
        """One scheduler round: refill every free slot from the queue
        (each free slot index gets at most one admission attempt per
        round), then run one batched decode step over the occupied slots.
        Returns a StepReport; with nothing occupied after admission the
        decode is skipped (decoded=0). The span `serve.round` covers the
        round, with the round's admissions and decoded rows as args."""
        with jax.profiler.TraceAnnotation("serve.round") as span:
            rep = self._round()
            span.set_metadata(admitted=len(rep.admitted), rows=rep.decoded)
        return rep

    def _round(self) -> StepReport:
        admitted: List[int] = []
        finished: List[int] = []
        for i in range(self.max_batch):
            if self._slots[i] is None and self._queue:
                r = self._queue.popleft()
                if r.max_new_tokens < 1:     # nothing to generate
                    self._per_req[r.rid] = RequestStats(
                        rid=r.rid, prompt_len=len(r.prompt),
                        new_tokens=0, queue_wait_s=0.0, ttft_s=0.0,
                        decode_steps=0, total_s=0.0, tok_per_s=0.0)
                    self._reqs.pop(r.rid, None)
                    finished.append(r.rid)
                    continue
                self._cache, slot, first = self._admit(
                    self._cache, i, r, self._t_enq[r.rid])
                self._n_prefills += 1
                self._out[r.rid].append(first)
                self._cur[i, 0] = first
                self._slots[i] = slot
                admitted.append(r.rid)
                if slot.remaining <= 0:      # max_new_tokens == 1
                    finished.append(self._finish(i))
        if not any(s is not None for s in self._slots):
            return StepReport(admitted=admitted, finished=finished,
                              decoded=0, queue_depth=len(self._queue))
        if self.spec_k:
            return self._spec_round(admitted, finished)
        active = np.array([s is not None for s in self._slots])
        with jax.profiler.TraceAnnotation("serve.decode"):
            logits, self._cache = self._decode(self.params, self._cache,
                                               jnp.asarray(self._cur),
                                               jnp.asarray(active))
        self._n_steps += 1
        self._slot_steps_active += int(active.sum())
        self._count_kv_lines(self._kv_blk)
        toks = self._sample_rows(logits, self._slots)
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            tok = int(toks[i])
            self._out[s.rid].append(tok)
            self._cur[i, 0] = tok
            s.n_gen += 1
            s.remaining -= 1
            s.decode_steps += 1
            if self._kv is not None:
                # decode growth: allocate pages as the row crosses a
                # page boundary (the written line is at pos-1; pos covers
                # prompt_len + n_gen lines)
                self._kv.grow(s.rid, s.prompt_len + s.n_gen)
            if s.remaining <= 0:
                finished.append(self._finish(i))
        return StepReport(admitted=admitted, finished=finished,
                          decoded=int(active.sum()),
                          queue_depth=len(self._queue))

    def _count_kv_lines(self, blk: int) -> None:
        """Count one decode step's K/V lines a layer: those it reads, each
        active row's lines rounded up to `blk` (every line of every slot
        where blk is 0), and those a whole-cache read takes. A row about
        to decode holds prompt_len + n_gen lines, its new one included."""
        full = self.max_batch * self.cache_len
        self._kv_lines_full += full
        if not blk:
            self._kv_lines_read += full
            return
        self._kv_lines_read += sum(
            -(-(s.prompt_len + s.n_gen) // blk) * blk
            for s in self._slots if s is not None)

    def _spec_round(self, admitted: List[int], finished: List[int]
                    ) -> StepReport:
        """One speculative scheduler round (replaces the plain batched
        decode when spec_k > 0): propose spec_k draft candidates per
        active slot, verify them all plus the committed current token in
        ONE target forward, accept host-side (serve/spec.py), then roll
        every slot's cache position back to its last accepted line.

        Position contract: a round starts with both caches' pos at the
        committed offset P = prompt_len + n_gen - 1 (cur's line unwritten,
        the plain-decode invariant). Propose advances the draft to
        P + spec_k + 1 (spec_k candidate feeds plus one catch-up feed that
        writes the last candidate's line — needed only on a full accept);
        verify advances the target to the speculated tip P + spec_k + 1.
        After accepting a tokens (+1 correction or bonus), BOTH roll back
        to P + a + 1. Rejected candidates' lines stay in the buffer beyond
        the committed region: invisible (cache_len masking) and
        overwritten by the step that first reaches them."""
        from repro.serve import spec as spec_lib
        k = self.spec_k
        active = np.array([s is not None for s in self._slots])
        act_j = jnp.asarray(active)
        n_active = int(active.sum())
        temps = np.array([s.temperature if s else 0.0 for s in self._slots],
                         np.float32)
        rids = np.array([s.rid if s else -1 for s in self._slots], np.int32)
        base_gen = np.array([s.n_gen if s else 0 for s in self._slots],
                            np.int32)
        # ---- propose: k sequential draft steps + the catch-up feed
        draft_toks = np.zeros((self.max_batch, k), np.int32)
        draft_logits = np.zeros((self.max_batch, k, self.cfg.vocab_size),
                                np.float32)
        feed = jnp.asarray(self._cur)
        for j in range(k):
            dlg, self._draft_cache = self._draft_decode(
                self.draft_params, self._draft_cache, feed, act_j)
            toks = np.asarray(self._spec_sample(
                dlg, jnp.asarray(temps), self.rng, jnp.asarray(rids),
                jnp.asarray(base_gen + j),
                jnp.int32(spec_lib.SALT_DRAFT)))
            draft_toks[:, j] = toks
            draft_logits[:, j] = np.asarray(dlg[:, 0], np.float32)
            feed = jnp.asarray(toks[:, None])
        _, self._draft_cache = self._draft_decode(
            self.draft_params, self._draft_cache, feed, act_j)
        # ---- verify: one (k+1)-position target forward over
        # [cur, d_0..d_{k-1}]; device pos advances to the speculated tip,
        # recorded in _spec_inflight so a mid-verify eviction (a fenced
        # replica) can roll back to the last accepted line
        for i, s in enumerate(self._slots):
            if s is not None:
                self._spec_inflight[i] = s.prompt_len + s.n_gen - 1
        vtoks = np.concatenate([self._cur, draft_toks], axis=1)
        vlg, self._cache = self._verify(self.params, self._cache,
                                        jnp.asarray(vtoks), act_j)
        vlg = np.asarray(vlg, np.float32)
        self._n_steps += 1
        self._slot_steps_active += n_active
        self._count_kv_lines(0)      # the verify reads every line
        self._spec["verify_steps"] += n_active
        self._spec["draft_steps"] += n_active * (k + 1)
        self._spec["proposed"] += n_active * k
        # ---- accept + commit (host), then roll positions back
        new_pos = np.asarray(self._cache["pos"]).copy()
        draft_pos = np.asarray(self._draft_cache["pos"]).copy()
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            emitted, kinds = spec_lib.accept_tokens(
                draft_toks[i], draft_logits[i], vlg[i],
                temperature=s.temperature, base_key=self.rng, rid=s.rid,
                n_gen=s.n_gen)
            # cap at the request budget; counters follow the kept tokens
            # so accepted + rejected + bonus == tokens_emitted survives
            m = min(len(emitted), s.remaining)
            emitted, kinds = emitted[:m], kinds[:m]
            self._out[s.rid].extend(emitted)
            self._cur[i, 0] = emitted[-1]
            s.n_gen += m
            s.remaining -= m
            s.decode_steps += 1
            for kind in kinds:
                self._spec[kind] += 1
            self._spec["tokens_emitted"] += m
            committed = s.prompt_len + s.n_gen - 1
            new_pos[i] = committed
            draft_pos[i] = committed
            if self._kv is not None:
                # page accounting covers committed lines only — the
                # speculative tip is never page-backed
                self._kv.grow(s.rid, s.prompt_len + s.n_gen)
            self._spec_inflight.pop(i, None)
            if s.remaining <= 0:
                finished.append(self._finish(i))
        self._cache["pos"] = jnp.asarray(new_pos)
        self._draft_cache["pos"] = jnp.asarray(draft_pos)
        return StepReport(admitted=admitted, finished=finished,
                          decoded=n_active, queue_depth=len(self._queue))

    def evict_inflight(self, rids: Optional[Iterable[int]] = None
                       ) -> Tuple[List[Request], int]:
        """Pull unfinished requests (occupied slots first, then the
        waiting queue) OUT of the engine. Two callers:

          * failover (rids=None): a router fencing a dead replica evicts
            EVERYTHING so survivors can re-serve it;
          * targeted eviction (rids={...}): the router's deadline sweep
            removes exactly the expired requests — batch-mates keep
            decoding undisturbed (their sample keys are per-request, so
            their streams cannot shift).

        Partial outputs and timing for the evicted rids are discarded — a
        re-queued request restarts from scratch, and the per-request
        fold_in(rid, i) sample keys make the restart token-for-token
        identical to an undisturbed run (the chaos-tier contract).
        Returns (evicted requests, tokens thrown away). The evicted
        slots' cache rows need no scrubbing: a freed slot's pos is held
        (its rows are masked) until the next admission overwrites them —
        PROVIDED the held pos never overstates the row's committed
        content. A spec engine evicted mid-verify violates that (device
        pos sits at the speculated tip), so spec slots roll back to the
        last ACCEPTED line here."""
        target = None if rids is None else set(rids)
        evicted: List[Request] = []
        wasted = 0
        for i, s in enumerate(self._slots):
            if s is None or (target is not None and s.rid not in target):
                continue
            if self.spec_k and self._cache is not None:
                # mid-verify eviction: roll the slot back to the last
                # accepted token, never the speculated tip (regression:
                # tests/test_spec_decode.py). _spec_inflight holds the
                # committed offset recorded at verify launch; outside a
                # round it is empty and the fallback equals device pos.
                committed = self._spec_inflight.pop(
                    i, s.prompt_len + s.n_gen - 1)
                self._cache["pos"] = \
                    self._cache["pos"].at[i].set(committed)
                self._draft_cache["pos"] = \
                    self._draft_cache["pos"].at[i].set(committed)
            evicted.append(self._reqs.pop(s.rid))
            wasted += len(self._out.pop(s.rid, []))
            self._t_enq.pop(s.rid, None)
            self._slots[i] = None
            if self._kv is not None:
                # slot eviction is this rid's terminal outcome here —
                # release exactly once (queued evictions below never
                # reached _admit, so they hold no pages)
                self._kv.release(s.rid)
        keep: deque = deque()
        while self._queue:
            r = self._queue.popleft()
            if target is not None and r.rid not in target:
                keep.append(r)
                continue
            evicted.append(self._reqs.pop(r.rid, r))
            wasted += len(self._out.pop(r.rid, []))
            self._t_enq.pop(r.rid, None)
        self._queue = keep
        self._n_submitted -= len(evicted)
        return evicted, wasted

    def finalize(self) -> Dict[str, Any]:
        """Aggregate this run's counters into the engine-stats dict
        (also stored on last_stats). run() calls it after draining; a
        stepwise driver calls it when it stops driving the engine."""
        wall = time.perf_counter() - self._t_start
        engine_stats = aggregate_engine_stats(
            self._per_req, n_requests=self._n_submitted,
            n_steps=self._n_steps, n_prefills=self._n_prefills,
            slot_steps_active=self._slot_steps_active,
            max_batch=self.max_batch, wall_s=wall)
        if self.mesh is not None:
            per_dev = self.device_stats()
            engine_stats["devices"] = len(per_dev)
            engine_stats["per_device"] = [
                {**d, "occupancy": engine_stats["occupancy"],
                 "tok_per_s": engine_stats["tok_per_s"]}
                for d in per_dev]
        # merged here (not in aggregate_engine_stats, whose schema is
        # pinned by tests/test_serve_stats.py): prompt tokens prefilled,
        # and the tokens of the padded buckets that prefill computed
        engine_stats["prefill_tokens"] = self._n_prefill_tokens
        engine_stats["prefill_bucket_tokens"] = self._n_prefill_bucket_tokens
        # K/V lines a layer the decode steps read, and those whole-cache
        # reads would take
        engine_stats["kv_lines_read"] = self._kv_lines_read
        engine_stats["kv_lines_full"] = self._kv_lines_full
        if self._kv is not None:
            # merged outside the pinned schema, like the counters above
            engine_stats["kvcache"] = self._kv.stats()
        if self.spec_k and self._spec is not None:
            # same pattern as kvcache: merged outside the pinned schema
            sp: Dict[str, Any] = dict(self._spec)
            sp["k"] = self.spec_k
            sp["acceptance_rate"] = (sp["accepted"] / sp["proposed"]
                                     if sp["proposed"] else 0.0)
            sp["accepted_tokens_per_step"] = (
                sp["tokens_emitted"] / sp["verify_steps"]
                if sp["verify_steps"] else 0.0)
            engine_stats["spec"] = sp
        self.last_stats = engine_stats
        return engine_stats

    def run(self, requests: List[Request], *, collect_stats: bool = False):
        """Serve requests with slot-level continuous batching. Returns
        {rid: generated tokens}, or (that, stats) with collect_stats=True.

        stats = {"requests": {rid: RequestStats}, "engine": {...}} — the
        engine dict is what last_stats holds after every run."""
        self.reset()
        for r in requests:
            self._queue.append(r)
            self._reqs[r.rid] = r
            self._t_enq[r.rid] = self._t_start
            self._out[r.rid] = []
        self._n_submitted = len(requests)
        while not self.idle:
            self.step()
        out = self._out
        engine_stats = self.finalize()
        if collect_stats:
            return out, {"requests": self._per_req, "engine": engine_stats}
        return out
