"""Static kernel auditor tests (docs/analysis.md).

Tiers here:
  * golden census — FLOPs and FMA-pairable FLOPs for every gpp version at
    TINY, pinned EXACTLY (the census is a deterministic jaxpr walk; a
    changed number means the kernel's arithmetic changed, which is
    precisely what the auditor exists to surface);
  * rule engine — each rule driven to fire via a minimal fake kernel fed
    straight to `audit_kernel` (no registry pollution);
  * the lint gate — the registry audits clean under --strict, and the
    deliberately-broken `fixture_badkernel` fails it with VMEM001;
  * tune-cache hygiene — validate/prune against a synthetic stale cache.
"""

import dataclasses
import json
import os
import subprocess
import sys
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np
import pytest

from repro.analyze import audit_registry
from repro.analyze.census import census_kernel
from repro.analyze.rules import RULES, audit_kernel
from repro.kernels import api
from repro.kernels.gpp import problem
from repro.tune import cache_tools, tuner

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS_DIR = os.path.join(REPO_ROOT, "tests")


# ---------------------------------------------------------------------------
# golden census: gpp v0–v10 at TINY, exact
# ---------------------------------------------------------------------------

# (total_flops, fma_pairable_flops) per version — regenerate with:
#   PYTHONPATH=src python -c "from repro.analyze.census import census_kernel;
#     from repro.kernels import api; from repro.kernels.gpp import problem;
#     [print(v, census_kernel(api.get_kernel('gpp'), v, problem.TINY).flops)
#      for v in api.get_kernel('gpp').versions]"
# The Pallas versions (v6+) include the packing of each grid instance's
# sums into its (8, 128) output tile.
GOLDEN_GPP_TINY = {
    "v0": (737544.0, 462984.0),
    "v1": (762120.0, 462984.0),
    "v2": (745736.0, 462984.0),
    "v3": (729352.0, 462984.0),
    "v4": (833752.0, 479368.0),
    "v5": (800920.0, 462984.0),
    "v6": (810584.0, 466056.0),
    "v7": (810584.0, 466056.0),
    "v8": (810584.0, 466056.0),
    "v9": (810584.0, 466056.0),
    "v10": (810584.0, 466056.0),
}


def test_gpp_census_golden():
    k = api.get_kernel("gpp")
    assert set(GOLDEN_GPP_TINY) == set(k.versions)
    for version, (flops, fma_flops) in GOLDEN_GPP_TINY.items():
        c = census_kernel(k, version, problem.TINY)
        assert c.flops == flops, (version, c.flops)
        assert c.fma_flops == fma_flops, (version, c.fma_flops)
        assert 0.0 < c.fma_fraction < 1.0
        # census agrees with the paper-derived analytic count within 2x
        assert 0.5 < c.flops / problem.TINY.total_flops() < 2.0


def test_gpp_census_pallas_structure():
    """The Pallas versions carry grid/VMEM structure the pure-JAX ones
    don't, and the census must see through scan+cond+pallas_call."""
    k = api.get_kernel("gpp")
    c = census_kernel(k, "v10", problem.TINY)
    assert c.grid_instances >= 1
    assert c.vmem_block_bytes and c.vmem_block_bytes > 0
    assert c.vmem_config_bytes and c.vmem_config_bytes > 0
    assert c.model_s is not None and c.model_s > 0
    assert c.bound_s > 0 and c.model_s > c.bound_s * 0.4
    assert c.float_dtypes == ("complex64", "float32")
    v0 = census_kernel(k, "v0", problem.TINY)
    assert v0.grid_instances == 0 and v0.vmem_block_bytes is None
    assert v0.hbm_bytes == c.hbm_bytes      # same planar operands/results


def test_flash_ssm_census():
    fk = api.get_kernel("flash")
    c = census_kernel(fk, "pallas", fk.canonical_keys()[0])
    assert c.dot_flops == 8388608.0          # 2 matmuls x 2BH x S^2 x hd x 2
    assert c.dot_flops / c.flops > 0.9       # attention is MXU-dominated
    assert "bfloat16" in c.float_dtypes      # operand dtype must be seen
    sk = api.get_kernel("ssm")
    s = census_kernel(sk, "pallas", sk.canonical_keys()[0])
    assert s.dot_flops == 0.0                # scan form never hits the MXU
    chunked = census_kernel(sk, "chunked", sk.canonical_keys()[0])
    assert chunked.dot_flops > 0             # chunk-parallel form does
    assert s.grid_instances == 2             # c=64 / blk_c=32... or menu


# ---------------------------------------------------------------------------
# rule engine via minimal fake kernels (fed straight to audit_kernel)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _Key:
    n: int = 64
    name: str = "fake"

    def key_dims(self) -> str:
        return str(self.n)


@dataclasses.dataclass(frozen=True)
class _Cfg:
    name: str = "cfg"
    blk: int = 8


class _FakeKernel(api.Kernel):
    name = "fake"
    versions = ("v",)
    default_version = "v"

    def static_config(self, key, version):
        return _Cfg()

    def make_example(self, key, seed=0):
        x = jnp.asarray(np.ones((key.n, key.n), np.float32))
        return (x,), {}

    def config_from_json(self, d):
        return _Cfg(**d)

    def run(self, x, *, version, config, interpret):
        return jnp.tanh(x) + x


def _rules_fired(k, key=_Key()):
    _, findings = audit_kernel(k, "v", key)
    return {f.rule for f in findings}, findings


def test_rule_vmem001():
    class K(_FakeKernel):
        def config_vmem_bytes(self, config, key):
            return 1 << 30                      # 1 GiB >> 16 MiB budget

    fired, findings = _rules_fired(K())
    assert fired == {"VMEM001"}
    f = [x for x in findings if x.rule == "VMEM001"][0]
    assert f.severity == "error" and "VMEM" in f.message


def test_rule_blk001():
    class K(_FakeKernel):
        def config_divides(self, config, key):
            return [f"n={key.n} not tiled by block 7"]

    fired, findings = _rules_fired(K())
    assert fired == {"BLK001"}
    assert findings[0].severity == "error"


def test_rule_dtype001():
    class K(_FakeKernel):
        def allowed_float_dtypes(self, version):
            return frozenset({"bfloat16"})      # but run computes in f32

    fired, findings = _rules_fired(K())
    assert fired == {"DTYPE001"}
    assert "float32" in findings[0].message


def test_rule_dup001():
    class K(_FakeKernel):
        def run(self, x, *, version, config, interpret):
            return x * x + x * x                # identical expensive muls

    fired, findings = _rules_fired(K())
    assert fired == {"DUP001"}
    assert findings[0].severity == "warning"    # advisory, not a gate fail


def test_rule_model001():
    class K(_FakeKernel):
        def model_step_s(self, key, config, version):
            return 1e-15                        # faster than the hardware

    fired, findings = _rules_fired(K())
    assert fired == {"MODEL001"}
    f = findings[0]
    assert f.severity == "error" and dict(f.data)["ratio"] < 0.4


def test_sane_model_no_drift():
    class K(_FakeKernel):
        def model_step_s(self, key, config, version):
            return 1.0                          # way above any bound: fine

    fired, _ = _rules_fired(K())
    assert fired == set()


# ---------------------------------------------------------------------------
# the lint gate: clean registry, broken fixture, CLI exit codes
# ---------------------------------------------------------------------------

def test_registry_audits_clean():
    """The acceptance bar: every registered (kernel, version, canonical
    shape) passes with zero error findings."""
    report = audit_registry()
    assert len(report.censuses) == sum(
        len(api.get_kernel(n).canonical_keys()) * len(api.get_kernel(n).versions)
        for n in api.list_kernels())
    assert report.errors == [], [f.row() for f in report.errors]
    payload = report.to_json()
    assert payload["schema"] == "repro-analyze/v1"
    assert payload["n_errors"] == 0
    assert set(payload["rules"]) == set(RULES)


def test_broken_fixture_fails_strict(tmp_path):
    """fixture_badkernel registers a VMEM-oversized kernel; the CLI must
    surface VMEM001 and --strict must exit nonzero (the CI gate works)."""
    from repro.analyze.__main__ import main
    sys.path.insert(0, TESTS_DIR)
    try:
        out = tmp_path / "report.json"
        rc = main(["--strict", "--no-cache", "--kernel", "badfix",
                   "--json", str(out), "--extra-module", "fixture_badkernel"])
        assert rc == 1
        payload = json.loads(out.read_text())
        rules_hit = {f["rule"] for f in payload["findings"]}
        assert "VMEM001" in rules_hit
        assert payload["n_errors"] >= 1
        # non-strict: same findings, but exit 0 (report-only mode)
        assert main(["--no-cache", "--kernel", "badfix"]) == 0
    finally:
        sys.path.remove(TESTS_DIR)
        api._REGISTRY.pop("badfix", None)


@pytest.mark.slow
def test_cli_strict_subprocess():
    """End-to-end: the exact invocation the CI static-analysis job runs
    exits 0 on the real registry, and nonzero with the broken fixture."""
    src = os.path.join(REPO_ROOT, "src")
    env = dict(os.environ, PYTHONPATH=src + os.pathsep + TESTS_DIR)
    ok = subprocess.run(
        [sys.executable, "-m", "repro.analyze", "--strict", "--no-cache"],
        capture_output=True, text=True, timeout=560, cwd=REPO_ROOT, env=env)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    bad = subprocess.run(
        [sys.executable, "-m", "repro.analyze", "--strict", "--no-cache",
         "--kernel", "badfix", "--extra-module", "fixture_badkernel"],
        capture_output=True, text=True, timeout=560, cwd=REPO_ROOT, env=env)
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "VMEM001" in bad.stdout


# ---------------------------------------------------------------------------
# tune-cache hygiene (validate / prune / CACHE001)
# ---------------------------------------------------------------------------

def _write_cache(tmp_path, entries):
    d = tmp_path / "tune"
    d.mkdir(exist_ok=True)
    (d / tuner.CACHE_FILE).write_text(json.dumps(entries))
    return str(d)


def _gpp_entry(version="v10", dims="64x8x8x2", blk_ig=64):
    return {"kernel": "gpp",
            "config": {"name": version, "blk_ig": blk_ig, "blk_igp": 8,
                       "blk_band": 8, "aqsm_transposed": True,
                       "fused_acc": True}}


def test_validate_cache_flags_stale(tmp_path):
    cache_dir = _write_cache(tmp_path, {
        "gpp|64x8x8x2|cpu|v10": _gpp_entry(),                  # valid
        "gpp|64x8x8x2|cpu|v99": _gpp_entry("v99"),             # gone version
        "gone|64x8x8x2|cpu|v1": _gpp_entry(),                  # gone kernel
        "gpp|64x8x8x2|cpu": _gpp_entry(),                      # malformed
        "gpp|64x8x8x2|tpu|v10": {"kernel": "gpp",              # bad config
                                 "config": {"name": "x", "nope": 1}},
    })
    issues = cache_tools.validate_cache(cache_dir)
    reasons = {i.key: i.reason for i in issues}
    assert reasons == {
        "gpp|64x8x8x2|cpu|v99": "unknown-version",
        "gone|64x8x8x2|cpu|v1": "unknown-kernel",
        "gpp|64x8x8x2|cpu": "malformed-key",
        "gpp|64x8x8x2|tpu|v10": "bad-config",
    }


def test_validate_cache_outside_space(tmp_path):
    # blk_ig=3 divides nothing in the menu: not a current candidate
    cache_dir = _write_cache(tmp_path, {
        "gpp|64x8x8x2|cpu|v10": _gpp_entry(blk_ig=3)})
    issues = cache_tools.validate_cache(cache_dir)
    assert [i.reason for i in issues] == ["outside-space"]
    assert issues[0].kernel == "gpp" and issues[0].version == "v10"


def test_validate_cache_clean_and_missing(tmp_path):
    assert cache_tools.validate_cache(str(tmp_path / "nope")) == []
    cache_dir = _write_cache(tmp_path, {
        "gpp|64x8x8x2|cpu|v10": _gpp_entry()})
    assert cache_tools.validate_cache(cache_dir) == []


def test_prune_cache(tmp_path):
    cache_dir = _write_cache(tmp_path, {
        "gpp|64x8x8x2|cpu|v10": _gpp_entry(),
        "gpp|64x8x8x2|cpu|v99": _gpp_entry("v99"),
    })
    with pytest.warns(UserWarning, match="v99"):
        kept, dropped = cache_tools.prune_cache(cache_dir)
    assert kept == 1 and [i.reason for i in dropped] == ["unknown-version"]
    left = json.loads((tmp_path / "tune" / tuner.CACHE_FILE).read_text())
    assert list(left) == ["gpp|64x8x8x2|cpu|v10"]
    assert cache_tools.validate_cache(cache_dir) == []


def test_prune_dry_run(tmp_path):
    cache_dir = _write_cache(tmp_path, {
        "gpp|64x8x8x2|cpu|v99": _gpp_entry("v99")})
    with pytest.warns(UserWarning):
        kept, dropped = cache_tools.prune_cache(cache_dir, dry_run=True)
    assert kept == 0 and len(dropped) == 1
    # dry run left the file untouched
    assert len(json.loads(
        (tmp_path / "tune" / tuner.CACHE_FILE).read_text())) == 1


def test_tune_cli(tmp_path):
    from repro.tune.__main__ import main
    cache_dir = _write_cache(tmp_path, {
        "gpp|64x8x8x2|cpu|v10": _gpp_entry(),
        "gpp|64x8x8x2|cpu|v99": _gpp_entry("v99"),
    })
    assert main(["validate", "--cache-dir", cache_dir]) == 1
    with pytest.warns(UserWarning):
        assert main(["prune", "--cache-dir", cache_dir]) == 0
    assert main(["validate", "--cache-dir", cache_dir]) == 0


def test_audit_registry_reports_cache_findings(tmp_path):
    cache_dir = _write_cache(tmp_path, {
        "gpp|64x8x8x2|cpu|v99": _gpp_entry("v99")})
    report = audit_registry(["ssm"], cache_dir=cache_dir)
    cache_findings = [f for f in report.findings if f.rule == "CACHE001"]
    assert len(cache_findings) == 1
    assert cache_findings[0].severity == "error"
    assert dict(cache_findings[0].data)["reason"] == "unknown-version"
    # and the validator is read-only: the stale entry is still there
    assert len(json.loads(
        (tmp_path / "tune" / tuner.CACHE_FILE).read_text())) == 1
