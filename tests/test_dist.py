"""Distribution-layer tests. Multi-device cases run in SUBPROCESSES with
XLA_FLAGS forcing 8 host devices — the main pytest process keeps the single
real CPU device (per the dry-run isolation contract)."""

import os
import subprocess
import sys
import textwrap
import types

import pytest
from _prop import given, settings, st

from repro.dist.sharding import ShardingPlan, spec_for
from repro.launch.mesh import make_mesh

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fake_mesh(**axes):
    return types.SimpleNamespace(shape=dict(axes))


def _plan(fsdp=False, kv=False, **axes):
    return ShardingPlan(mesh=_fake_mesh(**axes), dp_axes=tuple(
        a for a in ("pod", "data") if a in axes), fsdp=fsdp, kv_seq_shard=kv)


# ------------------------------------------------------------ spec_for rules

def test_tp_divisible_shards():
    p = _plan(data=16, model=16)
    spec = spec_for(p, ("d_model", "d_ff"), (1536, 8960))
    assert tuple(spec) == (None, "model")


def test_tp_fallback_replicates():
    """qwen2-1.5b: 12 heads / kv=2 don't divide 16 -> replicated."""
    p = _plan(data=16, model=16)
    assert tuple(spec_for(p, ("d_model", "heads"), (1536, 12 * 128))) \
        == (None, "model")  # 1536 lanes... heads dim = 12*128=1536 divisible!
    # a truly non-divisible dim:
    spec = spec_for(p, ("d_model", "kv_heads"), (1536, 2 * 3))
    assert tuple(spec) in ((), (None,), (None, None))


def test_batch_prefers_all_dp_axes():
    p = _plan(pod=2, data=16, model=16)
    spec = spec_for(p, ("batch", "seq"), (256, 4096), is_param=False)
    assert spec[0] == ("pod", "data")
    # batch=1 can't shard at all
    spec = spec_for(p, ("batch", "seq"), (1, 4096), is_param=False)
    assert tuple(spec) in ((), (None,), (None, None))


def test_kv_seq_shard_takes_model_axis_before_kv_heads():
    p = _plan(data=16, model=16, kv=True)
    spec = spec_for(p, ("layers", "batch", "kv_seq", "kv_heads", None),
                    (48, 128, 32768, 8, 128), is_param=False)
    assert spec[2] == "model"            # seq gets the model axis
    assert len(spec) < 4 or spec[3] is None   # kv_heads falls back


def test_fsdp_adds_dp_axes_to_largest_dim():
    p = _plan(data=16, model=16, fsdp=True)
    spec = spec_for(p, ("d_model", "d_ff"), (5120, 27648))
    # d_ff takes model; fsdp adds data onto the largest dim that divides
    flat = [a for s in spec if s for a in (s if isinstance(s, tuple) else (s,))]
    assert "data" in flat and "model" in flat


@settings(max_examples=40, deadline=None)
@given(
    dm=st.sampled_from([128, 1536, 5120, 6144]),
    dff=st.sampled_from([1408, 8960, 27648, 12345]),
    heads=st.sampled_from([2, 5, 8, 12, 16, 25, 40, 48]),
    fsdp=st.booleans(),
)
def test_spec_never_violates_divisibility(dm, dff, heads, fsdp):
    """Property: every mesh axis assigned to a dim divides that dim, and no
    mesh axis appears twice in one spec."""
    p = _plan(pod=2, data=16, model=16, fsdp=fsdp)
    axes = ("d_model", "d_ff", "heads", "batch")
    shape = (dm, dff, heads * 64, 64)
    spec = spec_for(p, axes, shape, is_param=True)
    used = []
    for i, s in enumerate(spec):
        if s is None:
            continue
        names = s if isinstance(s, tuple) else (s,)
        div = 1
        for a in names:
            assert a not in used, f"axis {a} reused in {spec}"
            used.append(a)
            div *= p.mesh.shape[a]
        assert shape[i] % div == 0, (spec, shape)


# --------------------------------------------------- fault-tolerance pieces

def test_heartbeat_roundtrip(tmp_path):
    from repro.dist.fault import HeartbeatFile
    hb = HeartbeatFile(str(tmp_path))
    assert hb.read() is None and hb.stale(1e9)
    hb.beat(7)
    b = hb.read()
    assert b["step"] == 7
    assert not hb.stale(60.0)
    assert hb.age_s() < 60.0


def test_heartbeat_clear_resets_liveness(tmp_path):
    """clear() hands the identity to a replacement process: the file is
    gone (reads as never-beaten / stale) and clearing twice is a no-op —
    the recovery path in serve/router.py relies on both."""
    from repro.dist.fault import HeartbeatFile
    hb = HeartbeatFile(str(tmp_path))
    hb.beat(5)
    assert hb.read() is not None
    hb.clear()
    assert hb.read() is None and hb.stale(1e9)
    hb.clear()                                 # idempotent on a missing file
    hb.beat(6)                                 # the replacement's first beat
    assert hb.read()["step"] == 6


def test_backoff_ticks_deterministic_exponential():
    """base * 2**(attempt-1), capped — pure integer arithmetic, so the
    router's retry schedule is a function of the attempt count alone."""
    from repro.dist.fault import backoff_ticks
    assert [backoff_ticks(k) for k in (1, 2, 3, 4, 5, 6)] \
        == [1, 2, 4, 8, 16, 32]
    assert [backoff_ticks(k, base=2, cap=12) for k in (1, 2, 3, 4)] \
        == [2, 4, 8, 12]
    assert backoff_ticks(60, base=3, cap=17) == 17   # no overflow blowup
    assert backoff_ticks(1, base=0) == 0             # immediate retry
    with pytest.raises(ValueError):
        backoff_ticks(0)
    with pytest.raises(ValueError):
        backoff_ticks(1, base=-1)


def test_heartbeat_staleness_survives_wall_clock_skew(tmp_path, monkeypatch):
    """NTP can step the wall clock in either direction between a beat and a
    supervisor poll; staleness math must run on CLOCK_MONOTONIC (shared by
    all processes within one boot), with the wall-clock field kept only
    for human-readable logs."""
    import time as _time

    from repro.dist import fault
    from repro.dist.fault import HeartbeatFile
    if fault._boot_id() is None:
        pytest.skip("no boot id: mono is never trusted on this platform")
    hb = HeartbeatFile(str(tmp_path))
    hb.beat(3)
    b = hb.read()
    assert b["step"] == 3 and "time" in b and "mono" in b
    # wall clock jumps 1h BACKWARDS after the beat: still fresh
    monkeypatch.setattr(_time, "time", lambda: b["time"] - 3600.0)
    assert hb.age_s() < 60.0
    assert not hb.stale(60.0)
    # ...and 1h FORWARDS: must not fake staleness either
    monkeypatch.setattr(_time, "time", lambda: b["time"] + 3600.0)
    assert not hb.stale(60.0)


def test_heartbeat_legacy_beat_falls_back_to_wall_clock(tmp_path):
    import json
    import time as _time

    from repro.dist.fault import HeartbeatFile
    hb = HeartbeatFile(str(tmp_path))
    with open(hb.path, "w") as fh:   # beat from an older worker: no "mono"
        json.dump({"step": 1, "time": _time.time() - 10.0}, fh)
    assert 5.0 < hb.age_s() < 60.0
    assert hb.stale(5.0) and not hb.stale(60.0)


def test_heartbeat_cross_boot_mono_falls_back_to_wall_clock(tmp_path):
    """CLOCK_MONOTONIC is per-boot: a beat written on another boot/host
    carries a mono value that is meaningless here (smaller OR larger than
    the reader's — either direction can fake freshness or staleness).
    Only a matching boot id makes mono trustworthy; otherwise staleness
    falls back to wall-clock age."""
    import json
    import time as _time

    from repro.dist.fault import HeartbeatFile
    hb = HeartbeatFile(str(tmp_path))
    # dead worker from a previous boot: huge mono, old wall time, no/other
    # boot id -> wall fallback says stale
    for boot in (None, "some-other-boot"):
        beat = {"step": 1, "time": _time.time() - 600.0,
                "mono": _time.monotonic() + 1e9}
        if boot:
            beat["boot"] = boot
        with open(hb.path, "w") as fh:
            json.dump(beat, fh)
        assert hb.age_s() > 300.0
        assert hb.stale(300.0)
    # live worker on another host (reader's uptime much larger): a naive
    # mono diff would be hugely positive -> must NOT fake staleness
    with open(hb.path, "w") as fh:
        json.dump({"step": 1, "time": _time.time() - 1.0,
                   "mono": _time.monotonic() - 1e9,
                   "boot": "some-other-boot"}, fh)
    assert not hb.stale(300.0)


def test_heartbeat_same_boot_future_mono_clamps_to_wall_clock(tmp_path):
    """Regression: a deserialized/hand-restored beat can carry THIS boot's
    id with a `mono` value ahead of the reader's clock — non-monotonic,
    impossible for a beat this kernel produced. The watchdog used to let
    the wall-clock fallback's max(0, ...) clamp such a beat to age 0
    whenever its wall time was also in the future, making a dead worker
    read fresh FOREVER. It must clamp to the wall-clock fallback path and
    read stale when that clock is untrustworthy too."""
    import json
    import time as _time

    from repro.dist import fault
    from repro.dist.fault import HeartbeatFile
    boot = fault._boot_id()
    if boot is None:
        pytest.skip("no boot id: mono is never trusted on this platform")
    hb = HeartbeatFile(str(tmp_path))
    # future mono, OLD wall time: falls back to the wall clock -> stale
    with open(hb.path, "w") as fh:
        json.dump({"step": 1, "time": _time.time() - 600.0,
                   "mono": _time.monotonic() + 1e6, "boot": boot}, fh)
    assert hb.age_s() > 300.0
    assert hb.stale(300.0)
    # future mono AND future wall time: wholly untrustworthy -> treated
    # as never-beaten (the bug: age clamped to 0.0, fresh forever)
    with open(hb.path, "w") as fh:
        json.dump({"step": 1, "time": _time.time() + 1e6,
                   "mono": _time.monotonic() + 1e6, "boot": boot}, fh)
    assert hb.age_s() is None
    assert hb.stale(300.0)
    # beat missing the wall-time field entirely must not crash the poll
    with open(hb.path, "w") as fh:
        json.dump({"step": 1, "mono": _time.monotonic() + 1e6,
                   "boot": boot}, fh)
    assert hb.age_s() is None and hb.stale(300.0)
    # a healthy beat still reads fresh through the mono path
    hb.beat(2)
    assert hb.age_s() < 60.0 and not hb.stale(60.0)


def test_watchdog_flags_straggler_after_warmup():
    from repro.dist.fault import StepWatchdog
    hits = []
    wd = StepWatchdog(on_straggler=lambda s, dt, ew: hits.append(s),
                      factor=3.0, warmup=3)
    assert not wd.observe(0, 30.0)          # compile step trains the EWMA
    for i in range(1, 6):
        assert not wd.observe(i, 1.0)
    assert wd.observe(6, 50.0)              # 50x the settled baseline
    assert hits == [6] and wd.stragglers[0][0] == 6
    assert not wd.observe(7, 1.0)           # one outlier didn't poison EWMA
    # a sustained slowdown (every step 40s vs ~12s EWMA) alarms at first,
    # then re-baselines instead of alarming forever
    flags = [wd.observe(8 + i, 40.0) for i in range(40)]
    assert flags[0], "sustained slowdown never flagged at all"
    assert not flags[-1], "watchdog never re-baselined"


def test_resume_or_init_fresh_and_resumed(tmp_path):
    from repro.ckpt.checkpoint import CheckpointManager
    from repro.dist.fault import resume_or_init
    import numpy as np
    mgr = CheckpointManager(str(tmp_path))
    step, state = resume_or_init(mgr, lambda: {"w": np.zeros(3)})
    assert step == 0 and state["w"].sum() == 0
    mgr.save(5, {"w": np.ones(3)}, blocking=True)
    step, state = resume_or_init(mgr, lambda: {"w": np.zeros(3)})
    assert step == 5 and state["w"].sum() == 3


def test_bubble_fraction_model():
    from repro.dist.pipeline import bubble_fraction
    assert bubble_fraction(1, 4) == 0.0              # no pipeline, no bubble
    assert abs(bubble_fraction(4, 4) - 3 / 7) < 1e-9
    # more microbatches amortize the fixed fill/drain cost
    assert bubble_fraction(4, 32) < bubble_fraction(4, 8)


def test_batch_and_params_sharding_trees():
    """Tree builders produce NamedSharding leaves (real 1-device mesh; the
    axis-assignment rules themselves are covered by the fakes above)."""
    from repro.dist.sharding import (ShardingPlan, batch_shardings,
                                     params_shardings)
    mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
    p = ShardingPlan(mesh=mesh, dp_axes=("pod", "data"))
    bs = batch_shardings(p, {"tokens": types.SimpleNamespace(shape=(256, 4096)),
                             "labels": types.SimpleNamespace(shape=(256, 4096)),
                             "cur_len": types.SimpleNamespace(shape=())})
    assert set(bs) == {"tokens", "labels", "cur_len"}
    assert tuple(bs["tokens"].spec)[0] == ("pod", "data")
    assert tuple(bs["cur_len"].spec) in ((), (None,))  # scalar replicates
    ps = params_shardings(
        p, {"ffn/wi": ("layers", "d_model", "d_ff")},
        {"ffn": {"wi": types.SimpleNamespace(shape=(48, 5120, 27648))},
         "norm": types.SimpleNamespace(shape=(48, 5120))})
    assert tuple(ps["ffn"]["wi"].spec) == (None, None, "model")
    assert tuple(ps["norm"].spec) in ((), (None,), (None, None))


# ------------------------------------------------- multi-device (subprocess)

def _run_sub(code: str):
    src = os.path.join(REPO_ROOT, "src")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=(src + os.pathsep + os.environ["PYTHONPATH"]
                           if os.environ.get("PYTHONPATH") else src))
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=560,
                       cwd=REPO_ROOT, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


@pytest.mark.slow
def test_flash_decode_matches_plain():
    _run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.models.attention import flash_decode_sharded, decode_attention
        from repro.models.layers import DistCtx
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = DistCtx(mesh=mesh)
        B,H,KvH,Hd,L = 4, 8, 2, 64, 256
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B,1,H,Hd), jnp.bfloat16)
        k = jax.random.normal(ks[1], (B,L,KvH,Hd), jnp.bfloat16)
        v = jax.random.normal(ks[2], (B,L,KvH,Hd), jnp.bfloat16)
        clen = jnp.int32(200)
        ref = decode_attention(q, k, v, clen)
        with jax.set_mesh(mesh):
            kd = jax.device_put(k, NamedSharding(mesh, P("data","model",None,None)))
            vd = jax.device_put(v, NamedSharding(mesh, P("data","model",None,None)))
            out = jax.jit(lambda q,k,v,c: flash_decode_sharded(q,k,v,c,ctx=ctx))(q,kd,vd,clen)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)-ref.astype(jnp.float32))))
        assert err < 5e-3, err
        print("flash decode ok", err)
    """)


@pytest.mark.slow
def test_int8_allreduce_close_to_exact():
    _run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.optim.compress import allreduce_int8, init_residual
        mesh = make_mesh((8,), ("data",))
        g = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, 64))}
        res = init_residual(g)
        with jax.set_mesh(mesh):
            gd = jax.device_put(g, {"w": NamedSharding(mesh, P("data", None))})
            # rank-major layout: row i is rank i's gradient
            out, res2 = jax.jit(
                lambda g, r: allreduce_int8(g, r, mesh, ("data",)))(gd, res)
        exact = np.asarray(g["w"]).mean(0)      # mean across ranks
        got = np.asarray(out["w"])              # every rank slot = the mean
        err = np.abs(got - exact[None]).max()
        assert err < 0.05, err
        print("int8 allreduce ok", err)
    """)


@pytest.mark.slow
def test_sharded_train_step_runs():
    """End-to-end: reduced model, debug mesh, 2 jitted sharded train steps
    (params+opt donated), loss finite and changing."""
    _run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs.base import get_config, reduce_config
        from repro.models.registry import build_model
        from repro.train import step as step_lib
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = reduce_config(get_config("phi4-mini-3.8b"))
        model = build_model(cfg)
        plan = step_lib.make_plan(cfg, mesh, kind="train")
        bundle, opt = step_lib.build_train_step(model, plan, microbatches=2)
        with jax.set_mesh(mesh):
            params = jax.jit(model.init_params,
                             out_shardings=bundle.in_shardings[0])(
                                 jax.random.PRNGKey(0))
            opt_state = jax.jit(opt.init,
                                out_shardings=bundle.in_shardings[1])(params)
            step = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                           out_shardings=bundle.out_shardings,
                           donate_argnums=bundle.donate_argnums)
            B, S = 4, 64
            batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B,S), 0, cfg.vocab_size),
                     "labels": jax.random.randint(jax.random.PRNGKey(1), (B,S), 0, cfg.vocab_size)}
            l0 = None
            for i in range(2):
                params, opt_state, m = step(params, opt_state, batch)
                l = float(m["loss"]); assert np.isfinite(l)
                if l0 is None: l0 = l
        assert l != l0, "params did not update"
        print("sharded train ok", l0, "->", l)
    """)


@pytest.mark.slow
def test_pipeline_parallel_matches_dense():
    _run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.dist.pipeline import pipelined_apply, bubble_fraction
        mesh = make_mesh((4,), ("model",))
        L, M, B, D = 8, 4, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        w = jax.random.normal(ks[0], (L, D, D)) * 0.1
        x = jax.random.normal(ks[1], (M, B, D))
        def layer(p, h):
            return jnp.tanh(h @ p)
        # dense reference
        def dense(x1):
            def body(c, p): return layer(p, c), None
            y, _ = jax.lax.scan(body, x1, w)
            return y
        ref = jax.vmap(dense)(x)
        with jax.set_mesh(mesh):
            out = jax.jit(lambda w, x: pipelined_apply(
                layer, w, x, mesh=mesh, pp_axis="model"))(w, x)
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err < 1e-4, err
        assert abs(bubble_fraction(4, 4) - 3/7) < 1e-9
        print("pipeline ok", err)
    """)


@pytest.mark.slow
def test_elastic_checkpoint_reshard():
    """Checkpoint written under one mesh restores onto a smaller mesh
    (elastic scaling: 8 -> 4 devices)."""
    _run_sub("""
        import jax, jax.numpy as jnp, numpy as np, tempfile, os
        from repro.launch.mesh import make_mesh
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.ckpt.checkpoint import CheckpointManager
        d = tempfile.mkdtemp()
        mgr = CheckpointManager(d)
        mesh8 = make_mesh((8,), ("model",))
        w = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
        with jax.set_mesh(mesh8):
            wd = jax.device_put(w, NamedSharding(mesh8, P("model", None)))
        mgr.save(1, {"w": wd}, blocking=True)
        # "lose half the fleet": restore onto a 4-device mesh
        devs = jax.devices()[:4]
        mesh4 = make_mesh((4,), ("model",), devices=devs)
        sh = {"w": NamedSharding(mesh4, P("model", None))}
        step, tree = mgr.restore(shardings=sh)
        assert step == 1
        np.testing.assert_array_equal(np.asarray(tree["w"]), np.asarray(w))
        assert len(tree["w"].sharding.device_set) == 4
        print("elastic reshard ok")
    """)
