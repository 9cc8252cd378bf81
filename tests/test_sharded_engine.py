"""Client-sharded SPMD engines: mesh helpers, bit-identity, parity.

Contracts pinned here (see core/round_engine.py ShardedRoundEngine):

* ``launch.mesh`` helpers: the host mesh clamps to divisors, the clients
  mesh raises past the visible devices, and mesh specs resolve;
* on a 1-DEVICE mesh the sharded step is BIT-IDENTICAL to the
  single-device ``BatchedRoundEngine`` (psum over one device is the
  identity, masks/QDQ fold GLOBAL fleet ids, and the Eq. (4) partials are
  the same arithmetic by construction);
* on multi-device meshes parity is allclose (per-shard partial sums then
  psum reorder the float32 reduction — the standard SPMD ulp caveat);
* the sparse collective's ``overflow`` certifies lossless compaction;
* the protocol and sim-runner routing/validation around ``mesh=``.

Multi-device cases run in a subprocess with
``--xla_force_host_platform_device_count`` so the main pytest process
keeps a single device (conftest policy)."""

import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.payload import (WireSpec, account_collective,
                                collective_payload_bytes)
from repro.core import round_engine
from repro.core.protocol import ProtocolConfig
from repro.core.selection import SelectionConfig
from repro.launch import mesh as mesh_mod

pytestmark = pytest.mark.flcore

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run_sub(code: str, devices: int = 8) -> str:
    env = {"XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
           "PYTHONPATH": SRC, "PATH": "/usr/bin:/bin"}
    import os
    env.update({k: v for k, v in os.environ.items()
                if k not in env and k != "XLA_FLAGS"})
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout


# ------------------------------------------------------- mesh helpers

def test_make_host_mesh_clamps_non_divisible_axes():
    """Axis sizes that do not divide the device count clamp to the
    largest divisor instead of erroring."""
    m = mesh_mod.make_host_mesh(data=3, model=1)   # 1 device visible
    assert m.devices.size == 1
    assert m.axis_names == ("data", "model")


def test_make_client_mesh_single_device():
    m = mesh_mod.make_client_mesh()
    assert m.axis_names == ("clients",)
    assert m.devices.size == jax.device_count()


def test_resolve_client_mesh_accepts_true_int_and_mesh():
    m_all = mesh_mod.resolve_client_mesh(True)
    assert m_all.devices.size == jax.device_count()
    m_one = mesh_mod.resolve_client_mesh(1)
    assert m_one.devices.size == 1
    assert mesh_mod.resolve_client_mesh(m_one) is m_one


@pytest.mark.parametrize("ask", [0, "visible+1", "visible*4"])
def test_client_mesh_raises_past_visible_devices(ask):
    """A mesh wider than the visible devices (or empty) raises instead of
    quietly narrowing — mesh=4 on one device must not run on one."""
    n = jax.device_count()
    k = {"visible+1": n + 1, "visible*4": 4 * n}.get(ask, ask)
    with pytest.raises(ValueError, match="device"):
        mesh_mod.make_client_mesh(k)
    with pytest.raises(ValueError, match="device"):
        mesh_mod.resolve_client_mesh(k)


def test_resolve_client_mesh_rejects_wrong_axis():
    import numpy as _np
    bad = jax.sharding.Mesh(_np.asarray(jax.devices()), ("pod",))
    with pytest.raises(ValueError):
        mesh_mod.resolve_client_mesh(bad)
    with pytest.raises(TypeError):
        mesh_mod.resolve_client_mesh("clients")


def test_host_mesh_non_divisible_counts_subprocess():
    """6 devices, data=4 requested -> clamps to 3 (largest divisor)."""
    code = """
    import jax
    from repro.launch.mesh import make_host_mesh, make_client_mesh
    m = make_host_mesh(data=4, model=1)
    assert m.shape["data"] == 3, dict(m.shape)
    c = make_client_mesh(4)
    assert c.devices.size == 4 and c.axis_names == ("clients",)
    print("OK")
    """
    assert "OK" in _run_sub(code, devices=6)


# ------------------------------------------- engine-level bit identity

def _fleet(n=10, seed=0):
    k = jax.random.PRNGKey(seed)
    gparams = {"w": jax.random.normal(jax.random.fold_in(k, 0), (4, 8)),
               "b": jax.random.normal(jax.random.fold_in(k, 1), (8,))}
    stacked = jax.tree_util.tree_map(
        lambda l: jnp.stack([l * (1 + 0.01 * i) for i in range(n)]), gparams)
    new = jax.tree_util.tree_map(lambda l: l * 1.01 + 0.002, stacked)
    d = jnp.asarray(np.linspace(0.0, 0.6, n), jnp.float32)
    w = jnp.asarray(np.arange(1, n + 1), jnp.float32)
    return gparams, stacked, new, d, w


def test_one_device_mesh_is_bit_identical_to_batched_engine():
    gparams, stacked, new, d, w = _fleet()
    cfg = SelectionConfig()
    base = round_engine.BatchedRoundEngine(cfg)
    shard = round_engine.ShardedRoundEngine(
        cfg, base.comm, mesh=mesh_mod.make_client_mesh(1))
    rk = jax.random.PRNGKey(3)
    for fr, dm in [(False, False), (True, False), (False, True)]:
        o1 = base.step(stacked, new, gparams, d, w, rk,
                       full_round=fr, dense_masks=dm)
        o2 = shard.step(stacked, new, gparams, d, w, rk,
                        full_round=fr, dense_masks=dm)
        for a, b in zip(jax.tree_util.tree_leaves(o1.global_params),
                        jax.tree_util.tree_leaves(o2.global_params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(o1.client_params),
                        jax.tree_util.tree_leaves(o2.client_params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(o1.densities),
                                      np.asarray(o2.densities))


def test_sharded_engine_rejects_overrides_and_bad_config():
    gparams, stacked, new, d, w = _fleet()
    eng = round_engine.ShardedRoundEngine(
        SelectionConfig(), mesh=mesh_mod.make_client_mesh(1))
    with pytest.raises(NotImplementedError):
        eng.step(stacked, new, gparams, d, w, jax.random.PRNGKey(0),
                 full_round=False, stacked_upload=new)
    with pytest.raises(ValueError):
        round_engine.ShardedRoundEngine(SelectionConfig())   # no mesh
    with pytest.raises(ValueError):
        round_engine.ShardedRoundEngine(
            SelectionConfig(), mesh=mesh_mod.make_client_mesh(1),
            collective="ring")
    with pytest.raises(ValueError):
        round_engine.ShardedRoundEngine(
            SelectionConfig(), mesh=mesh_mod.make_client_mesh(1),
            keep_fraction=0.0)


# ------------------------------------------------ multi-device parity

def test_eight_device_parity_dense_and_sparse():
    """13 clients (non-divisible) over 8 devices: allclose to the
    single-device engine for the dense psum and the kf=1.0 sparse route;
    sparse kf<1 with bounded dropout stays lossless (overflow 0)."""
    code = """
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import round_engine
    from repro.core.selection import SelectionConfig
    from repro.launch import mesh as mesh_mod

    n = 13
    k = jax.random.PRNGKey(0)
    gparams = {"w": jax.random.normal(jax.random.fold_in(k, 0), (4, 8)),
               "b": jax.random.normal(jax.random.fold_in(k, 1), (8,))}
    stacked = jax.tree_util.tree_map(
        lambda l: jnp.stack([l * (1 + 0.01 * i) for i in range(n)]),
        gparams)
    new = jax.tree_util.tree_map(lambda l: l * 1.01 + 0.002, stacked)
    w = jnp.asarray(np.arange(1, n + 1), jnp.float32)
    rk = jax.random.PRNGKey(3)
    cfg = SelectionConfig()
    base = round_engine.BatchedRoundEngine(cfg)
    m = mesh_mod.make_client_mesh()
    assert m.devices.size == 8

    def check(eng, d, expect_overflow_zero=True):
        o1 = base.step(stacked, new, gparams, d, w, rk, full_round=False)
        o2 = eng.step(stacked, new, gparams, d, w, rk, full_round=False)
        for a, b in zip(jax.tree_util.tree_leaves(o1.global_params),
                        jax.tree_util.tree_leaves(o2.global_params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-6, atol=2e-6)
        np.testing.assert_array_equal(np.asarray(o1.densities),
                                      np.asarray(o2.densities))
        if o2.collective_overflow is not None and expect_overflow_zero:
            assert float(o2.collective_overflow) == 0.0

    d_mixed = jnp.asarray(np.linspace(0.0, 0.6, n), jnp.float32)
    check(round_engine.ShardedRoundEngine(cfg, base.comm, mesh=m), d_mixed)
    check(round_engine.ShardedRoundEngine(cfg, base.comm, mesh=m,
                                          collective="sparse",
                                          keep_fraction=1.0), d_mixed)
    # high uniform dropout: every client keeps ceil(8*0.25)=2 channels,
    # any shard's union of <= 2 clients is <= 4 <= K=ceil(8*0.8)=7
    d_hi = jnp.full((n,), 0.75, jnp.float32)
    check(round_engine.ShardedRoundEngine(cfg, base.comm, mesh=m,
                                          collective="sparse",
                                          keep_fraction=0.8), d_hi)
    # low dropout overflows the K=7 buffer: certificate > 0
    eng = round_engine.ShardedRoundEngine(cfg, base.comm, mesh=m,
                                          collective="sparse",
                                          keep_fraction=0.8)
    o = eng.step(stacked, new, gparams, jnp.zeros((n,), jnp.float32), w,
                 rk, full_round=False)
    assert float(o.collective_overflow) > 0.0
    print("OK")
    """
    assert "OK" in _run_sub(code)


def test_grouped_sharded_parity_eight_devices():
    """Ragged fleet: grouped engine with a mesh matches the unsharded
    grouped step (allclose; densities exact)."""
    code = """
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import round_engine as re_mod, coverage as cov_mod
    from repro.core.selection import SelectionConfig
    from repro.fl.heterogeneity import group_by_shape
    from repro.launch import mesh as mesh_mod

    rng = jax.random.PRNGKey(0)
    n = 10
    gparams = {"w1": jax.random.normal(jax.random.fold_in(rng, 0), (4, 8)),
               "b1": jax.random.normal(jax.random.fold_in(rng, 1), (8,))}
    def sub(p, frac):
        return jax.tree_util.tree_map(
            lambda l: l[tuple(slice(0, max(1, int(s * frac)))
                              for s in l.shape)], p)
    cp = [sub(gparams, 1.0) if i % 2 == 0 else sub(gparams, 0.5)
          for i in range(n)]
    cp = [jax.tree_util.tree_map(lambda l, i=i: l * (1 + 0.01 * i), p)
          for i, p in enumerate(cp)]
    full_w = cov_mod.channel_widths(gparams, -1)
    cw = [cov_mod.channel_widths(p, -1) for p in cp]
    cr = cov_mod.coverage_rates(cw, full_w)
    groups = group_by_shape(cp)
    coverage = [cov_mod.coverage_pytree(cp[g.indices[0]], cr, -1)
                for g in groups]
    batches = []
    for g, cov in zip(groups, coverage):
        stacked = re_mod.stack_pytrees([cp[i] for i in g.indices])
        new = jax.tree_util.tree_map(lambda l: l * 1.01 + 0.002, stacked)
        batches.append(re_mod.GroupBatch(
            indices=jnp.asarray(g.indices, jnp.int32),
            stacked_old=stacked, stacked_new=new, coverage=cov,
            dropout=jnp.asarray([0.3] * g.size, jnp.float32)))
    w = jnp.asarray(np.arange(1, n + 1), jnp.float32)
    rk = jax.random.PRNGKey(3)
    cfg = SelectionConfig()
    base = re_mod.GroupedRoundEngine(cfg)
    shard = re_mod.GroupedRoundEngine(cfg, base.comm,
                                      mesh_mod.make_client_mesh())
    for fr, dm in [(False, False), (True, False), (False, True)]:
        o1 = base.step(batches, gparams, w, rk, full_round=fr,
                       dense_masks=dm)
        o2 = shard.step(batches, gparams, w, rk, full_round=fr,
                        dense_masks=dm)
        for a, b in zip(jax.tree_util.tree_leaves(o1.global_params),
                        jax.tree_util.tree_leaves(o2.global_params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-6, atol=2e-6)
        np.testing.assert_array_equal(np.asarray(o1.densities),
                                      np.asarray(o2.densities))
    print("OK")
    """
    assert "OK" in _run_sub(code)


def test_sharded_fleet_unstack_train_restack_four_devices():
    """A fleet placed on a 4-device ``clients`` mesh (as the sharded
    executor and the sim runner place it) unstacks, trains through a
    per-client jitted function and restacks with the compiled helpers;
    every client and the restacked fleet equal the same calls on one
    device, and the eager definitions, bit for bit."""
    code = """
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import round_engine as re_mod
    from repro.core.selection import SelectionConfig
    from repro.launch import mesh as mesh_mod

    n = 8
    k = jax.random.PRNGKey(0)
    fleet = [{"w": jax.random.normal(jax.random.fold_in(k, i), (4, 8)),
              "b": jnp.full((8,), 0.1 * i), "loss": jnp.float32(i)}
             for i in range(n)]
    one = re_mod.stack_pytrees(fleet)
    eng = re_mod.ShardedRoundEngine(SelectionConfig(),
                                    mesh=mesh_mod.make_client_mesh())
    assert eng.num_shards == 4
    sharded = jax.device_put(one, eng.shard_spec())
    train = jax.jit(lambda p, i: jax.tree_util.tree_map(
        lambda l: l * 1.01 + 0.003 * i, p))

    def cycle(stacked):
        clients = re_mod.unstack_pytree(stacked, n)
        return clients, re_mod.stack_pytrees(
            [train(p, i) for i, p in enumerate(clients)])

    def eager_cycle(stacked):
        clients = [jax.tree_util.tree_map(lambda l: l[i], stacked)
                   for i in range(n)]
        return clients, jax.tree_util.tree_map(
            lambda *ls: jnp.stack(ls),
            *[train(p, i) for i, p in enumerate(clients)])

    def same(a, b):
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            assert (x.shape, x.dtype) == (y.shape, y.dtype)
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    mesh_devices = set(eng.mesh.devices.flat)
    c_mesh, s_mesh = cycle(sharded)
    c_one, s_one = cycle(one)
    c_eager, s_eager = eager_cycle(sharded)
    for a, b, c, orig in zip(c_mesh, c_one, c_eager, fleet):
        same(a, b)
        same(a, c)
        same(a, orig)
        for leaf in jax.tree_util.tree_leaves(a):
            assert leaf.sharding.device_set <= mesh_devices
    same(s_mesh, s_one)
    same(s_mesh, s_eager)
    print("OK")
    """
    assert "OK" in _run_sub(code, devices=4)


# --------------------------------------------------- protocol routing

def _telemetry(n=13, seed=0):
    from repro.core.allocation import ClientTelemetry
    rng = np.random.default_rng(seed)
    return ClientTelemetry(
        model_bytes=np.full(n, 4096.0),
        uplink_rate=rng.uniform(1e3, 5e3, n),
        downlink_rate=rng.uniform(5e3, 2e4, n),
        compute_latency=rng.uniform(1.0, 5.0, n),
        num_samples=rng.integers(10, 50, n).astype(float),
        label_coverage=rng.uniform(0.5, 1.0, n),
        train_loss=np.ones(n))


def _gparams():
    k = jax.random.PRNGKey(42)
    return {"w": jax.random.normal(k, (4, 8)), "b": jnp.zeros((8,))}


def _btrain(stacked, rng):
    new = jax.tree_util.tree_map(lambda l: l * 1.01 + 0.003, stacked)
    n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    return new, jnp.ones((n,))


def test_protocol_mesh_one_bit_identical_to_engine_executor():
    from repro.core.protocol import FedDDServer
    tel = _telemetry()

    def run(**kw):
        cfg = ProtocolConfig(selection=SelectionConfig(), rounds=4,
                             seed=0, **kw)
        srv = FedDDServer(_gparams(), cfg, tel)
        srv.run(batched_train_fn=_btrain)
        return srv

    s0, s1 = run(), run(mesh=1)
    for a, b in zip(jax.tree_util.tree_leaves(s0.global_params),
                    jax.tree_util.tree_leaves(s1.global_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_protocol_mesh_wider_than_visible_devices_raises():
    """ProtocolConfig(mesh=4) on fewer than 4 devices fails at run time
    instead of running a narrower mesh."""
    from repro.core.protocol import FedDDServer
    cfg = ProtocolConfig(selection=SelectionConfig(), rounds=1, seed=0,
                         mesh=4 * jax.device_count())
    srv = FedDDServer(_gparams(), cfg, _telemetry())
    with pytest.raises(ValueError, match="device"):
        srv.run(batched_train_fn=_btrain)


def test_protocol_config_mesh_validations():
    sel = SelectionConfig()
    with pytest.raises(ValueError):
        ProtocolConfig(selection=sel, mesh=1, rounds_per_dispatch=2)
    with pytest.raises(ValueError):
        ProtocolConfig(selection=sel, mesh=1, mesh_collective="ring")
    with pytest.raises(ValueError):
        ProtocolConfig(selection=sel, mesh=1, mesh_keep_fraction=0.0)


def test_protocol_mesh_requires_engine_backed_execution():
    from repro.core.protocol import FedDDServer
    cfg = ProtocolConfig(selection=SelectionConfig(), mesh=1,
                         batched=False, rounds=2)
    srv = FedDDServer(_gparams(), cfg, _telemetry())
    with pytest.raises(ValueError):
        srv.run(local_train_fn=lambda p, i, r: (p, 1.0))


def test_protocol_eight_device_parity_subprocess():
    code = """
    import numpy as np, jax, jax.numpy as jnp
    from repro.core.protocol import ProtocolConfig, FedDDServer
    from repro.core.selection import SelectionConfig
    from repro.core.allocation import ClientTelemetry

    n = 13
    rng = np.random.default_rng(0)
    tel = ClientTelemetry(
        model_bytes=np.full(n, 4096.0),
        uplink_rate=rng.uniform(1e3, 5e3, n),
        downlink_rate=rng.uniform(5e3, 2e4, n),
        compute_latency=rng.uniform(1.0, 5.0, n),
        num_samples=rng.integers(10, 50, n).astype(float),
        label_coverage=rng.uniform(0.5, 1.0, n),
        train_loss=np.ones(n))

    def params():
        k = jax.random.PRNGKey(42)
        return {"w": jax.random.normal(k, (4, 8)), "b": jnp.zeros((8,))}

    def btrain(stacked, rng_):
        new = jax.tree_util.tree_map(lambda l: l * 1.01 + 0.003, stacked)
        return new, jnp.ones((stacked["w"].shape[0],))

    def run(**kw):
        cfg = ProtocolConfig(selection=SelectionConfig(), rounds=4,
                             seed=0, **kw)
        srv = FedDDServer(params(), cfg, tel)
        srv.run(batched_train_fn=btrain)
        return srv

    s0 = run()
    for kw in (dict(mesh=True),
               dict(mesh=True, mesh_collective="sparse",
                    mesh_keep_fraction=1.0)):
        s = run(**kw)
        for a, b in zip(jax.tree_util.tree_leaves(s0.global_params),
                        jax.tree_util.tree_leaves(s.global_params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-6, atol=2e-6)
    print("OK")
    """
    assert "OK" in _run_sub(code)


# -------------------------------------------------- sim-runner routing

def test_sim_mesh_one_bit_identical():
    from repro.core.allocation import ClientTelemetry  # noqa: F401
    from repro.sim.runner import SimConfig, run_sim
    tel = _telemetry()

    def train(p, i, r):
        return jax.tree_util.tree_map(lambda l: l * 1.01 + 0.003, p), 1.0

    r0 = run_sim("feddd", _gparams(), tel, train, rounds=3, seed=0)
    r1 = run_sim("feddd", _gparams(), tel, train, rounds=3, seed=0,
                 mesh=1)
    for a, b in zip(jax.tree_util.tree_leaves(r0.global_params),
                    jax.tree_util.tree_leaves(r1.global_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert [h.sim_time for h in r0.history] == \
        [h.sim_time for h in r1.history]


def test_sim_mesh_guards():
    from repro.sim.faults import RandomFaults
    from repro.sim.runner import SimConfig, run_sim
    tel = _telemetry()

    def train(p, i, r):
        return p, 1.0

    with pytest.raises(ValueError):
        run_sim("feddd", _gparams(), tel, train, rounds=2, mesh=1,
                faults=RandomFaults(corrupt_rate=0.5))
    with pytest.raises(ValueError):
        run_sim("feddd", _gparams(), tel, train, rounds=2, mesh=1,
                sim=SimConfig(policy="deadline",
                              policy_kw={"partial": True}))
    # ragged fleet + sparse collective: grouped reduces dense-only
    cp = [_gparams() if i % 2 == 0 else
          jax.tree_util.tree_map(lambda l: l[..., :4], _gparams())
          for i in range(13)]
    with pytest.raises(ValueError):
        run_sim("feddd", _gparams(), tel, train, rounds=2, mesh=1,
                client_params=cp, mesh_collective="sparse",
                mesh_keep_fraction=0.5)


# --------------------------------------------- collective byte model

def test_collective_payload_bytes_dense_vs_sparse():
    spec = WireSpec(((8, 32), (8, 8)))
    dense = collective_payload_bytes(spec, mode="dense")
    # full f32 numerator + (C,) den profile per leaf
    assert dense == (32 + 8) * 4.0 + (8 + 8) * 4.0
    sparse = collective_payload_bytes(spec, mode="sparse", k_fraction=0.5)
    # K=4 rows of elements/C values + K idx + K den rows, per leaf
    assert sparse == (4 * 4 * 4.0 + 4 * 8.0) + (4 * 1 * 4.0 + 4 * 8.0)
    assert sparse < dense
    with pytest.raises(ValueError):
        collective_payload_bytes(spec, mode="ring")


def test_account_collective_hooks_recorder():
    class _Rec:
        active = True

        def __init__(self):
            self.calls = []

        def collective(self, dense, wire):
            self.calls.append((dense, wire))

    spec = WireSpec(((8, 32),))
    rec = _Rec()
    dense, actual = account_collective(spec, 4, mode="sparse",
                                       k_fraction=0.5, obs=rec)
    assert rec.calls == [(dense, actual)]
    assert dense == 4 * collective_payload_bytes(spec, mode="dense")
    assert actual < dense
    d2, a2 = account_collective(spec, 4, mode="dense")
    assert d2 == a2
