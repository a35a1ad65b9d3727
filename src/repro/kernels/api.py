"""Unified kernel registry: one dispatch/tune/bench API for every kernel
family (DESIGN.md §Kernel registry).

The paper's 8-step methodology — census the kernel, model it on the
roofline, tune block shapes, verify by measurement — was only wired up for
the GPP kernel; flash attention shipped frozen blocks and the ssm scan had
no public op layer. This module makes the journey a *protocol*:

  * `Kernel` — descriptor for one kernel family: named, versioned
    implementations (pure-JAX reference → Pallas), a `ProblemKey` for
    cache keying, a tunable config space with clamping rules, and an
    analytic roofline-model hook (the tuner's ranking function).
  * `ProblemKey` — the protocol replacing the GPP-only `GppSize` in the
    tune cache: anything with a `.name` and `.key_dims()`.
  * a process-wide registry: `register(kernel)`, `get_kernel(name)`,
    `list_kernels()`, and `dispatch(name, *args, version=, config=,
    interpret=, **kwargs)` — the single public entry point.

A kernel registered here joins `repro.tune` (generalized cache keyed
`(kernel, ProblemKey, backend, version)`) and the bench trajectory
(`benchmarks/run.py kernel_tuner` + per-row config provenance) for free.
Backend policy (interpret autodetect + REPRO_INTERPRET) is shared via
`repro.backend` — kernels never carry a private `_on_tpu()`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Protocol, Tuple, runtime_checkable


@runtime_checkable
class ProblemKey(Protocol):
    """What the tune cache keys on: a named problem instance whose
    `key_dims()` string is stable across processes (dims only — never
    id()s or hashes that vary per run)."""

    name: str

    def key_dims(self) -> str:
        """e.g. '8192x1024x1024x2' — joined into the JSON cache key."""
        ...


class Kernel:
    """Descriptor for one kernel family. Subclasses fill in the class
    attributes and override the hooks their family supports; everything a
    kernel leaves at the default still dispatches — it just won't tune
    (empty config space) or model (no roofline hook).

    Class attributes:
      name             registry key ('gpp', 'flash', 'ssm')
      versions         ordered implementation names, reference → fastest
      default_version  what dispatch runs when version=None
      tunable          versions whose config comes from repro.tune when
                       dispatch is called without an explicit config
    """

    name: str = ""
    versions: Tuple[str, ...] = ()
    default_version: str = ""
    tunable: Tuple[str, ...] = ()

    # -- identity / cache keying ------------------------------------------
    def problem_key(self, *args, **kwargs) -> ProblemKey:
        """Recover the ProblemKey from a dispatch call's arguments."""
        raise NotImplementedError

    # -- config space (the tuner's menu) ----------------------------------
    def config_space(self, key: ProblemKey, version: str) -> List[Any]:
        """Feasible configs for `key` (divisibility-exact, VMEM-feasible),
        deterministic order. Empty = nothing to tune."""
        return []

    def clamp(self, config: Any, key: ProblemKey) -> Any:
        """Shrink a config to fit a smaller problem."""
        return config

    def static_config(self, key: ProblemKey, version: str) -> Optional[Any]:
        """The frozen per-version config (e.g. GPP v6–v9), clamped to
        `key`; None when the version takes no config or must be tuned."""
        return None

    def tie_break(self, config: Any) -> Tuple:
        """Deterministic sort tail for model-score ties (gpp: bigger
        blocks first — fewer grid instances)."""
        return ()

    def finalize_config(self, config: Any, version: str) -> Any:
        """Stamp the winning config before it is cached (gpp renames it
        to the version)."""
        return config

    # -- roofline model hook ----------------------------------------------
    def model_step_s(self, key: ProblemKey, config: Any,
                     version: str) -> float:
        """Analytic modeled step seconds — the tuner's ranking function
        and the journey's reporting model."""
        raise NotImplementedError(f"{self.name} has no roofline model")

    # -- measurement hooks -------------------------------------------------
    def measure_ok(self, key: ProblemKey) -> bool:
        """Whether CPU (interpret-mode) timing is cheap enough for this
        problem; on TPU the tuner always measures."""
        return False

    def make_example(self, key: ProblemKey, seed: int = 0
                     ) -> Tuple[tuple, dict]:
        """(args, kwargs) for a representative dispatch of `key`, for the
        tuner's measurement pass."""
        raise NotImplementedError(f"{self.name} cannot synthesize inputs")

    # -- config (de)serialization for the JSON tune cache ------------------
    def config_to_json(self, config: Any) -> Dict:
        return dataclasses.asdict(config)

    def config_from_json(self, d: Dict) -> Any:
        raise NotImplementedError

    # -- static-analysis hooks (repro.analyze, docs/analysis.md) -----------
    def canonical_keys(self) -> List["ProblemKey"]:
        """Representative shapes the `repro.analyze` auditor censuses this
        family at (small enough to trace on CPU; one golden shape per
        family is pinned in tests). Empty = the auditor skips the family."""
        return []

    def key_from_dims(self, dims: str) -> "ProblemKey":
        """Inverse of `ProblemKey.key_dims()` — rebuild the key from its
        cache-dims string so the tune-cache validator can re-derive the
        current config space for a cached entry. Kernels that don't
        implement it only get existence (not config-space) validation."""
        raise NotImplementedError(f"{self.name} cannot parse key dims")

    def config_vmem_bytes(self, config: Any, key: "ProblemKey"
                          ) -> Optional[int]:
        """Analytic VMEM working set of `config` at `key` (double-buffered
        inputs + live intermediates), checked against the hw budget by the
        auditor's VMEM001 rule. None = no VMEM model for this family."""
        return None

    def gather_buffer_bytes(self, config: Any, key: "ProblemKey"
                            ) -> Optional[int]:
        """For kernels that gather operands through an index (paged decode's
        block-table K/V fetch): the double-buffered gather-block bytes that
        MUST be part of `config_vmem_bytes`. The auditor's KV001 rule flags
        a kernel that declares gather buffers its VMEM model doesn't cover
        (the working set would pass VMEM001 while overflowing at runtime).
        None = the family gathers nothing (no check)."""
        return None

    def config_divides(self, config: Any, key: "ProblemKey") -> List[str]:
        """Divisibility violations of `config` at `key` — one human-readable
        string per axis the blocks cannot tile (BLK001 is raised for each).
        Called on the *clamped* config: non-empty means the clamp rules
        cannot repair this (config, problem) pair."""
        return []

    def allowed_float_dtypes(self, version: str) -> frozenset:
        """Float/complex dtype names this version's compute path may touch;
        any other float dtype in the traced jaxpr is a DTYPE001 leak (f32
        ops inside a declared-f64 path and vice versa). Empty = unchecked."""
        return frozenset()

    # -- execution ---------------------------------------------------------
    def run(self, *args, version: str, config: Any,
            interpret: Optional[bool], **kwargs) -> Any:
        """Run `version` under `config` (already resolved by dispatch;
        config may be None for versions that need none). Must resolve
        interpret through repro.backend, never a private check."""
        raise NotImplementedError


# ---------------------------------------------------------------------------
# process-wide registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Kernel] = {}
_BUILTINS_LOADED = False


def register(kernel: Kernel) -> Kernel:
    """Add a kernel to the registry (last registration wins, so tests can
    shadow a builtin). Returns the kernel for decorator-ish use."""
    if not kernel.name:
        raise ValueError("kernel.name must be set")
    if kernel.default_version not in kernel.versions:
        raise ValueError(f"{kernel.name}: default_version "
                         f"{kernel.default_version!r} not in versions")
    _REGISTRY[kernel.name] = kernel
    return kernel


def _ensure_builtins() -> None:
    """Import the builtin kernel families exactly once. Deferred so that
    `import repro.kernels.api` stays cheap and the kernel_def modules can
    import repro.tune/backend without a cycle. The flag is only set on
    success — a failed import stays visible (and retryable) instead of
    leaving a silently partial registry."""
    global _BUILTINS_LOADED
    if _BUILTINS_LOADED:
        return
    from repro.kernels.flash import kernel_def as _f    # noqa: F401
    from repro.kernels.gpp import kernel_def as _g      # noqa: F401
    from repro.kernels.live_decode import kernel_def as _l  # noqa: F401
    from repro.kernels.paged import kernel_def as _p    # noqa: F401
    from repro.kernels.ssm import kernel_def as _s      # noqa: F401
    _BUILTINS_LOADED = True


def get_kernel(name: str) -> Kernel:
    """Look up a registered Kernel descriptor by name — the object that
    knows a family's versions, problem keys, config space, and roofline
    model (docs/kernels.md documents the full protocol). Raises KeyError
    listing what IS registered for an unknown name.

    Example::

        import repro
        gpp = repro.get_kernel("gpp")
        gpp.versions            # ('v0', ..., 'v10')
        gpp.default_version     # 'v10'
    """
    _ensure_builtins()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown kernel {name!r}; registered: "
                       f"{sorted(_REGISTRY)}") from None


def list_kernels() -> List[str]:
    """Sorted names of every registered kernel family. Importing this
    module registers the builtins lazily, so the list is complete without
    importing the kernel packages yourself.

    Example::

        import repro
        repro.list_kernels()    # ['flash', 'gpp', 'ssm']
    """
    _ensure_builtins()
    return sorted(_REGISTRY)


def dispatch(name: str, *args, version: Optional[str] = None,
             config: Any = None, interpret: Optional[bool] = None,
             problem_key: Any = None, **kwargs) -> Any:
    """Run kernel `name` on `args` — the one public entry point for every
    registered kernel family.

    version=None uses the kernel's default; config=None resolves per
    version — the frozen static config (clamped) for static versions, the
    repro.tune cached winner for tunable ones. interpret=None defers to
    repro.backend (REPRO_INTERPRET override). Extra kwargs are the
    kernel's own (e.g. flash's causal=); a name the kernel doesn't accept
    raises TypeError rather than being swallowed.

    problem_key: optional pre-built ProblemKey overriding the one derived
    from args — SPMD callers use it to tune for the LOCAL shard of a
    problem whose operands are still global at trace time (e.g. the
    sharded ServeEngine keys the ssm scan on channels/tp so cached block
    configs match what each device actually executes).

    Under `jax.profiler` the call shows as the host span
    `kernel.dispatch` (args `name`, `version`), holding `kernel.config`
    while the config resolves, and the kernel's device ops carry the
    named scope `kernel.<name>`.

    Example::

        import repro
        from repro.kernels.gpp import problem
        ach, asx = repro.dispatch("gpp", problem.make_inputs(problem.TINY))
    """
    import jax
    k = get_kernel(name)
    version = version or k.default_version
    if version not in k.versions:
        raise ValueError(f"unknown {k.name} version {version!r}; "
                         f"have {list(k.versions)}")
    with jax.profiler.TraceAnnotation("kernel.dispatch", name=k.name,
                                      version=version):
        if config is None:
            with jax.profiler.TraceAnnotation("kernel.config"):
                config = _resolve_config(k, version, problem_key, args,
                                         kwargs)
        with jax.named_scope(f"kernel.{k.name}"):
            return k.run(*args, version=version, config=config,
                         interpret=interpret, **kwargs)


def _resolve_config(k: Kernel, version: str, problem_key: Any,
                    args: tuple, kwargs: dict) -> Any:
    """The config `dispatch` runs when the caller gives none."""
    key = problem_key if problem_key is not None \
        else k.problem_key(*args, **kwargs)
    if version in k.tunable and k.config_space(key, version):
        from repro.tune import tuner    # deferred: tune is optional here
        return tuner.tune_kernel(k.name, key, version=version).config
    # static versions, and tunable ones at shapes the candidate menu
    # can't tile (empty space): the clamped static config — the legacy
    # entry points' behavior for odd sizes
    config = k.static_config(key, version)
    if config is None and version in k.tunable:
        raise ValueError(f"no feasible {k.name} config for {key}")
    return config
