"""Shared model building blocks.

Conventions:
  * params are nested dicts of jnp arrays; every leaf has *logical axes*
    recorded by ParamBuilder (e.g. ("layers","d_model","d_ff")) which the
    sharding engine (repro.dist.sharding) later maps onto mesh axes.
  * matmuls run in bf16 with f32 accumulation (preferred_element_type),
    norms/softmax in f32 — the production mixed-precision policy.
  * layer stacks are scanned (jax.lax.scan) over a leading "layers" axis so
    the HLO stays compact for 64-layer configs (critical for 40-cell x
    2-mesh dry-run compile times).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

PyTree = Any
Axes = Tuple[Optional[str], ...]

PARAM_DTYPE = jnp.bfloat16
NORM_DTYPE = jnp.float32


class ParamBuilder:
    """Creates parameters and records their logical axes in a parallel tree.

    Usage:
        b = ParamBuilder(rng)
        w = b.param("attn/wq", (L, D, H*Hd), ("layers","d_model","heads"))
    `b.axes` afterwards maps path -> logical axes for the sharding engine.
    Set `abstract=True` to emit ShapeDtypeStructs (dry-run init, no memory).
    """

    def __init__(self, rng: Optional[jax.Array], abstract: bool = False,
                 scale: float = 0.02):
        self._rng = rng
        self.abstract = abstract
        self.scale = scale
        self.axes: Dict[str, Axes] = {}

    def _next_rng(self):
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def param(self, path: str, shape: Tuple[int, ...], axes: Axes,
              init: str = "normal", dtype=PARAM_DTYPE):
        assert len(shape) == len(axes), (path, shape, axes)
        self.axes[path] = axes
        if self.abstract:
            return jax.ShapeDtypeStruct(shape, dtype)
        if init == "zeros":
            return jnp.zeros(shape, dtype)
        if init == "ones":
            return jnp.ones(shape, dtype)
        if init == "normal":
            return (self.scale * jax.random.normal(
                self._next_rng(), shape, jnp.float32)).astype(dtype)
        if init == "uniform":  # for decay-style params
            return jax.random.uniform(
                self._next_rng(), shape, jnp.float32, -1.0, 1.0).astype(dtype)
        if init == "a_log":  # mamba: A = -arange(1..N) broadcast over channels
            n = shape[-1]
            row = jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))
            return jnp.broadcast_to(row, shape).astype(dtype)
        if init.startswith("const:"):
            return jnp.full(shape, float(init.split(":")[1]), dtype)
        raise ValueError(init)


def set_path(tree: Dict, path: str, leaf):
    parts = path.split("/")
    for p in parts[:-1]:
        tree = tree.setdefault(p, {})
    tree[parts[-1]] = leaf


def build_params(fn: Callable[[ParamBuilder], Dict],
                 rng: Optional[jax.Array], abstract: bool = False):
    """Run a builder fn, returning (params_tree, axes_by_path)."""
    b = ParamBuilder(rng, abstract=abstract)
    tree = fn(b)
    return tree, b.axes


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------

def matmul(x, w, *, out_dtype=PARAM_DTYPE):
    """bf16 x bf16 -> f32 accumulate -> cast. The MXU-native contraction."""
    x = isolate_exact_tp(x)
    y = jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return isolate_exact_tp(y.astype(out_dtype))


# --- exact-TP (bit-exact sharded serving) ----------------------------------
# Trace-time marker set by ServeEngine while tracing its sharded steps
# (dist.sharding.serve_specs plans). Inside the scope the row-parallel
# matmuls all-gather their activation back to replicated BEFORE
# contracting, instead of letting GSPMD psum per-shard partials: with the
# serve plan's column-parallel-only weights, every float reduction then
# runs in single-device association order and the sharded engine is
# bit-exact vs the unsharded one (the psum's shard-order reduction is the
# one thing that breaks that, by ~1 bf16 ulp — enough to flip an argmax).
_EXACT_TP_MESH = None


class exact_tp_scope:
    """Context manager marking a trace as exact-TP over `mesh` (None is a
    no-op scope, so callers can use it unconditionally)."""

    def __init__(self, mesh):
        self.mesh = mesh

    def __enter__(self):
        global _EXACT_TP_MESH
        self._prev = _EXACT_TP_MESH
        _EXACT_TP_MESH = self.mesh
        return self

    def __exit__(self, *exc):
        global _EXACT_TP_MESH
        _EXACT_TP_MESH = self._prev
        return False


def gather_exact_tp(x):
    """All-gather x to replicated when tracing under exact_tp_scope (the
    pre-contraction gather of the exact-TP combine), under the named scope
    `tp.gather`; identity otherwise."""
    if _EXACT_TP_MESH is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec
    with jax.named_scope("tp.gather"):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(_EXACT_TP_MESH, PartitionSpec()))


def isolate_exact_tp(x):
    """Pass x through an optimization barrier, under the named scope
    `tp.fence`, when tracing under exact_tp_scope; identity otherwise.

    Bit-exactness across TP degrees also needs every device to compute an
    op the same way whatever its local width. On TPU, XLA picks fusions
    and layouts from the per-device shapes, and a projection or an
    attention core fused with its neighbours can change its accumulation
    order with the width: codeqwen1.5-7b decode logits differed by up to
    0.03 between TP4 and TP2 on v5e, although each op alone gives the same
    bits at both widths. Fencing the activations into and out of the
    projections and the attention core keeps each out of its neighbours'
    fusions (the weights stay unfenced, so their slices still stream into
    the dots), and TP4 and TP2 then match bit for bit."""
    if _EXACT_TP_MESH is None:
        return x
    with jax.named_scope("tp.fence"):
        return jax.lax.optimization_barrier(x)


def matmul_rp(x, w):
    """Row-parallel projection (contracting dim sharded over `model`): emit
    bf16 so the cross-shard psum XLA inserts moves HALF the bytes (the
    Megatron bf16-allreduce trick; local MXU accumulation is still f32 —
    only the cross-chip combine is bf16).

    Under exact_tp_scope (sharded serving) the activation is gathered
    first and the weight is replicated by plan, so this contraction is
    computed whole per device — bit-exact, no psum."""
    x = gather_exact_tp(isolate_exact_tp(x))
    y = jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.bfloat16)
    return isolate_exact_tp(y)


def einsum(subscript, *ops, out_dtype=PARAM_DTYPE):
    y = jnp.einsum(subscript, *ops, preferred_element_type=jnp.float32)
    return y.astype(out_dtype)


def rms_norm(x, scale, eps: float):
    xf = x.astype(NORM_DTYPE)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(NORM_DTYPE)).astype(x.dtype)


def layer_norm(x, scale, bias, eps: float):
    xf = x.astype(NORM_DTYPE)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(NORM_DTYPE) + bias.astype(NORM_DTYPE)).astype(x.dtype)


def swiglu(x, wi, wg, wo):
    """SwiGLU FFN: silu(x@wg) * (x@wi) @ wo. The down-projection is
    row-parallel (d_ff sharded) -> bf16 before the psum."""
    h = matmul(x, wi)
    g = matmul(x, wg)
    h = h * jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype)
    return matmul_rp(h, wo)


def gelu_ffn(x, wi, bi, wo, bo):
    h = matmul(x, wi) + bi.astype(PARAM_DTYPE)
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(PARAM_DTYPE)
    return matmul_rp(h, wo) + bo.astype(PARAM_DTYPE)


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, Hd); positions: (S,) or (..., S) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (Hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., S, Hd/2)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return y.astype(x.dtype)


def sinusoid_pos(seq: int, d: int, offset=0):
    pos = jnp.arange(offset, offset + seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    inv = jnp.exp(-jnp.log(10000.0) * dim / d)
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(PARAM_DTYPE)


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------

def embed(tokens, table):
    return jnp.take(table, tokens, axis=0)


def lm_logits(x, table_or_head):
    """Project to vocab in f32 (loss numerics).

    Under exact_tp_scope the rows are flattened, and a single row (the
    last prefill token) is padded to 8: XLA emits a one-row product
    differently at different vocab shard widths, an 8-row one the same
    way."""
    def proj(a):
        return jax.lax.dot_general(
            a, table_or_head, (((a.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if _EXACT_TP_MESH is None:
        return proj(x)
    lead = x.shape[:-1]
    rows = isolate_exact_tp(x).reshape(-1, x.shape[-1])
    n = rows.shape[0]
    if n == 1:
        rows = isolate_exact_tp(jnp.pad(rows, ((0, 7), (0, 0))))
    return isolate_exact_tp(proj(rows)[:n].reshape(*lead, -1))


def softmax_xent(logits_f32, labels, *, z_loss: float = 1e-4):
    """Cross-entropy with optional z-loss (PaLM-style logit regularizer).
    logits: (..., V) f32; labels: (...) int32. Returns per-token loss."""
    lse = jax.scipy.special.logsumexp(logits_f32, axis=-1)
    ll = jnp.take_along_axis(logits_f32, labels[..., None], axis=-1)[..., 0]
    loss = lse - ll
    if z_loss:
        loss = loss + z_loss * jnp.square(lse)
    return loss


@dataclasses.dataclass
class DistCtx:
    """How a model apply should interact with the mesh (None = single host).
    kv_seq_shard: decode attention uses the distributed flash-decode path
    (KV cache seq dim sharded over `model_axis`, partial-softmax psum).
    ep_data: MoE uses the shard_map all-to-all expert-parallel dispatch
    (moe.moe_ffn_ep) for large token counts."""
    mesh: Any = None
    data_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"
    kv_seq_shard: bool = False
    ep_data: bool = False

    @property
    def model_size(self) -> int:
        if self.mesh is None:
            return 1
        return self.mesh.shape[self.model_axis]

    def tp_shards(self, *dims: int) -> int:
        """How many ways the model axis splits dims that are all divisible
        by it (1 when any isn't — the spec_for replication fallback).
        Kernel call sites use this to key tuned configs on the LOCAL
        per-shard problem (dim // tp_shards) instead of the global shape."""
        tp = self.model_size
        if tp > 1 and all(d % tp == 0 for d in dims):
            return tp
        return 1
