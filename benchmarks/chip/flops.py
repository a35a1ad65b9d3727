"""Operations and bytes that each measured program's algorithm needs,
computed from shapes alone. Kept with the benchmark, so a change to the
program cannot move the yardstick.

The counts are of the work the algorithm needs, not of what today's
program does: a decode step reads every weight once and the K/V of the
live tokens only, so a program that reads dead cache lines, or holds a
weight twice, shows a lower share of its roofline.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Mapping

# --------------------------------------------------------------------- GPP
# Copied from the program's GPP problem definition: 90 FLOP per inner
# iteration of the branchless form, counted on the planar-f32 arithmetic
# (wdiff 2, |wdiff|^2 3, reciprocal 1, delw 8, |delw|^2 3, branch 1: sch 6,
# cden 5, |cden|^2 and reciprocal 4, ssx 10; branch 2: cden 8, ssx 12;
# selects ~8; mat 6; accumulation 16).
GPP_FLOPS_PER_ITER = 90.0


@dataclasses.dataclass(frozen=True)
class GppShape:
    nbands: int
    ngpown: int
    ncouls: int
    nw: int

    @classmethod
    def of(cls, cfg: Mapping) -> "GppShape":
        return cls(int(cfg["nbands"]), int(cfg["ngpown"]),
                   int(cfg["ncouls"]), int(cfg["nw"]))


def gpp_flops(s: GppShape) -> float:
    return float(s.nbands * s.ngpown * s.ncouls * s.nw) * GPP_FLOPS_PER_ITER


def gpp_min_bytes(s: GppShape) -> float:
    """Compulsory HBM traffic: every planar-f32 input read once, the
    complex outputs written once."""
    b = 2 * 4 * s.ncouls * s.ngpown * 2      # wtilde, I_eps
    b += 2 * 4 * s.ncouls * s.nbands         # aqsn
    b += 2 * 4 * s.ngpown * s.nbands         # aqsm
    b += 4 * s.nw * s.nbands                 # wx
    b += 4 * s.ncouls                        # vcoul
    b += 2 * 4 * s.nw * 2                    # achtemp, asxtemp
    return float(b)


# --------------------------------------------------------------- dense LM
@dataclasses.dataclass(frozen=True)
class DenseShape:
    """A dense decoder (RMSNorm, GQA with RoPE, SwiGLU) by its published
    widths."""
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    tied: bool
    qkv_bias: bool
    weight_bytes: int = 2          # bf16 weights
    kv_bytes: int = 2              # bf16 cache lines

    @classmethod
    def of(cls, cfg: Mapping) -> "DenseShape":
        return cls(layers=int(cfg["num_hidden_layers"]),
                   d=int(cfg["hidden_size"]),
                   heads=int(cfg["num_attention_heads"]),
                   kv_heads=int(cfg["num_key_value_heads"]),
                   head_dim=int(cfg["head_dim"]),
                   ff=int(cfg["intermediate_size"]),
                   vocab=int(cfg["vocab_size"]),
                   tied=bool(cfg["tie_word_embeddings"]),
                   qkv_bias=bool(cfg["qkv_bias"]))

    @property
    def layer_matmul_params(self) -> int:
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        return 2 * self.d * q + 2 * self.d * kv + 3 * self.d * self.ff

    @property
    def layer_params(self) -> int:
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        bias = (q + 2 * kv) if self.qkv_bias else 0
        return self.layer_matmul_params + bias + 2 * self.d

    def param_count(self) -> int:
        emb = self.vocab * self.d
        return (emb * (1 if self.tied else 2)
                + self.layers * self.layer_params + self.d)

    @property
    def kv_line_bytes(self) -> int:
        """Bytes of one token's K and V across all layers."""
        return self.layers * 2 * self.kv_heads * self.head_dim * self.kv_bytes

    def attn_flops(self, ctx: int) -> float:
        """Scores and weighted sum of one query against ctx keys, all
        layers."""
        return 4.0 * self.layers * self.heads * self.head_dim * ctx


def decode_step_flops(s: DenseShape, ctx_lens: Iterable[int]) -> float:
    """One batched decode step: every active row (one per entry of
    ctx_lens, its live context including the new token) through every
    projection and the LM head, and attention over its live context."""
    per_row = 2.0 * (s.layers * s.layer_matmul_params + s.vocab * s.d)
    return sum(per_row + s.attn_flops(c) for c in ctx_lens)


def decode_step_bytes(s: DenseShape, ctx_lens: Iterable[int]) -> float:
    """Every parameter byte read once (an untied embedding table is only
    gathered, one row per active row), the live K/V of the active rows
    read, and each row's new K/V line written."""
    ctx_lens = list(ctx_lens)
    rows = len(ctx_lens)
    params = s.param_count()
    if not s.tied:
        params -= s.vocab * s.d
        params += rows * s.d
    live = sum(ctx_lens) - rows              # lines written before this step
    return (params * s.weight_bytes + live * s.kv_line_bytes
            + rows * s.kv_line_bytes)


def prefill_flops(s: DenseShape, n_tokens: int) -> float:
    """Prefill of one prompt of n_tokens real tokens: every token through
    every layer, causal attention over its prefix, and the LM head for the
    last position only (the one whose logits are sampled)."""
    n = int(n_tokens)
    proj = 2.0 * n * s.layers * s.layer_matmul_params
    attn = 2.0 * s.layers * s.heads * s.head_dim * n * (n + 1)
    return proj + attn + 2.0 * s.vocab * s.d


def roofline_s(flops: float, nbytes: float, *, chips: int, peak_flops: float,
               hbm_bw: float) -> float:
    """Least time the chips could take for this work, split evenly over
    them: the larger of the compute and the memory bound."""
    return max(flops / (chips * peak_flops), nbytes / (chips * hbm_bw))
