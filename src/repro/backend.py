"""Shared backend policy: one place that decides whether Pallas kernels
compile for a real TPU or run in interpret mode.

Before the kernel registry, `kernels/gpp/ops.py` and `kernels/flash/ops.py`
each carried a private `_on_tpu()` and `kernels/ssm/ssm_scan.py` hardcoded
`interpret=True` — three policies that could (and did) drift. Every kernel
entry point now resolves its `interpret` default through this module.

Env override: `REPRO_INTERPRET=1` forces interpret mode even on TPU (kernel
debugging), `REPRO_INTERPRET=0` forces compiled mode (fails fast on CPU
rather than silently interpreting). Unset: autodetect (interpret iff no TPU).
A JAX that cannot initialize its backend raises here; it is never read as
"no TPU".

The module also places JAX's persistent compilation cache for the entry
points (`enable_compile_cache`).
"""

from __future__ import annotations

import os
from typing import Optional

INTERPRET_ENV = "REPRO_INTERPRET"
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <repo root>/.jax_cache: a fixed path, because the cache key includes it
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def backend_name() -> str:
    import jax
    return jax.default_backend()


def on_tpu() -> bool:
    import jax
    return jax.devices()[0].platform == "tpu"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory. Where $JAX_COMPILATION_CACHE_DIR is set, JAX
    reads it and nothing is set here; otherwise the cache goes to
    <repo root>/.jax_cache."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR


def default_interpret() -> bool:
    """Interpret-mode default for Pallas calls: the REPRO_INTERPRET env
    override when set ('1'/'true' -> True, '0'/'false' -> False),
    otherwise autodetect (interpret iff not on a TPU)."""
    env = os.environ.get(INTERPRET_ENV)
    if env is not None:
        v = env.strip().lower()
        if v in ("1", "true", "yes"):
            return True
        if v in ("0", "false", "no"):
            return False
        raise ValueError(f"{INTERPRET_ENV}={env!r}: expected 0/1")
    return not on_tpu()


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """An explicit caller choice wins; None defers to default_interpret()."""
    return default_interpret() if interpret is None else bool(interpret)
