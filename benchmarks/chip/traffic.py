"""The one traffic generator. A traffic mix is a data file,
`traffic/<mix>.json`, of parameters this module reads; a new mix needs a
new file and no code.

Open-loop mixes (`"kind": "open_loop"`) give a schedule of requests, each
with the wall-clock offset at which it is due. The sizes and arrivals come
from the mix's own fixed `shape_seed`, so every run of a cell offers the
same work: the same multiset of gaps, prompt lengths and output lengths.
The run's `--seed` only puts them in another order, within consecutive
blocks of 8 requests, and draws the token ids. The draw logic (lognormal lengths, Poisson gaps) follows the
program's synthetic trace generator, in seconds of wall clock and with ids
from the model's whole vocabulary.

Closed-loop mixes (`"kind": "closed_loop"`) describe callers that each
wait for their reply before sending the next call; they have no schedule.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Dict, List, Mapping

import numpy as np

from benchmarks.chip.common import HERE


def load_mix(name: str, root: str = HERE) -> Dict:
    with open(os.path.join(root, "traffic", f"{name}.json")) as fh:
        return json.load(fh)


def seed_stream(seed: int, *salt: int) -> np.random.Generator:
    """A numpy generator from any whole-number seed (any size, sign
    included), with optional salts for independent streams."""
    seed = int(seed)
    return np.random.default_rng([abs(seed), int(seed < 0), *salt])


@dataclasses.dataclass(frozen=True)
class ScheduledRequest:
    rid: int
    due_s: float             # offset from the start of the window
    prompt: np.ndarray       # (P,) int32
    max_new_tokens: int
    temperature: float


def lengths(rng: np.random.Generator, spec: Mapping, n: int) -> np.ndarray:
    """n discrete lengths from spec: a lognormal of the given median and
    sigma, rounded and clipped to [min, max]."""
    if spec.get("dist", "lognormal") != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    draw = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.round(draw), spec["min"], spec["max"]).astype(np.int64)


def arrivals(rng: np.random.Generator, mix: Mapping, seconds: float
             ) -> np.ndarray:
    """Gaps of a Poisson process at rate_per_s, conditioned on
    round(rate * seconds) arrivals inside the window: exponential gaps
    scaled so that one more gap would end the window."""
    if mix.get("arrival", "poisson") != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrival']!r}")
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    gaps = rng.exponential(1.0, n + 1)
    return gaps[:n] * (seconds / gaps.sum())


BLOCK = 8     # requests whose order a seed may change among themselves


def block_permutation(rng: np.random.Generator, x: np.ndarray, block: int
                      ) -> np.ndarray:
    """x with each run of `block` consecutive entries shuffled: every seed
    offers the same work in each stretch of the window, so the work done
    inside the window does not depend on the seed."""
    out = np.array(x)
    for i in range(0, len(out), block):
        out[i:i + block] = rng.permutation(out[i:i + block])
    return out


def schedule(mix: Mapping, seed: int, seconds: float, vocab: int
             ) -> List[ScheduledRequest]:
    """The open-loop schedule of one run: requests due inside
    [0, seconds), ordered by due time."""
    if mix["kind"] != "open_loop":
        raise ValueError(f"mix kind {mix['kind']!r} has no schedule")
    shape = seed_stream(int(mix["shape_seed"]))
    gaps = arrivals(shape, mix, seconds)
    n = len(gaps)
    plens = lengths(shape, mix["prompt_len"], n)
    olens = lengths(shape, mix["output_len"], n)
    order = seed_stream(seed, 1)
    gaps, plens, olens = (block_permutation(order, x, BLOCK)
                          for x in (gaps, plens, olens))
    due = np.cumsum(gaps) - gaps[0]          # the first request opens the window
    ids = seed_stream(seed, 2)
    temp = float(mix.get("temperature", 0.0))
    return [ScheduledRequest(
        rid=i, due_s=float(due[i]),
        prompt=ids.integers(0, vocab, int(plens[i])).astype(np.int32),
        max_new_tokens=int(olens[i]), temperature=temp)
        for i in range(n)]
