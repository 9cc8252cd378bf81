"""Shape-grouped round engine vs the per-client reference loop.

The grouped engine (core/round_engine.py GroupedRoundEngine) is the
heterogeneous hot path: clients partitioned by sub-model shape, one fused
jit step per shape census.  These tests pin its contracts:

* loop parity — on a ragged 3-width fleet, feddd runs (h-period full
  rounds included, Eq. (21) coverage rectification active) reproduce the
  loop's masks and history and its global and client params to a few
  float32 ulps (LOOP_RTOL / LOOP_ATOL: two compiled programs);
* baselines — dense grouped rounds match the loop to float tolerance
  (summation order differs, as for the homogeneous engine);
* sim integration — run_sim accepts ragged fleets; sync + static
  reproduces the closed-form driver exactly; deadline/async compose;
* determinism — same seed gives identical results in any process
  (subprocess digests, mirroring tests/test_sim.py).
"""

import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import aggregation, coverage as cov_mod, run_scheme, selection
from repro.core.round_engine import (GroupBatch, GroupedRoundEngine,
                                     stack_pytrees, unstack_pytree)
from repro.core.selection import SelectionConfig

pytestmark = pytest.mark.flcore

WIDTHS = (12, 8, 6)           # ragged 3-width fleet, two clients per width


def _sub_params(key, w):
    k1, k2 = jax.random.split(key)
    return {"fc0": {"w": jax.random.normal(k1, (20, w)), "b": jnp.zeros(w)},
            "fc1": {"w": jax.random.normal(k2, (w, 5)), "b": jnp.zeros(5)}}


def _ragged_fleet(n=6, seed=0):
    """n clients cycling the three widths (non-contiguous groups)."""
    gp = _sub_params(jax.random.PRNGKey(seed), max(WIDTHS))
    clients = [_sub_params(jax.random.PRNGKey(seed + 100 + i),
                           WIDTHS[i % len(WIDTHS)]) for i in range(n)]
    return gp, clients


def _tel_for(clients, seed=0):
    from repro.core.allocation import ClientTelemetry
    n = len(clients)
    rng = np.random.default_rng(seed)
    nbytes = [float(sum(l.size * l.dtype.itemsize
                        for l in jax.tree_util.tree_leaves(p)))
              for p in clients]
    return ClientTelemetry(
        model_bytes=np.asarray(nbytes),
        uplink_rate=rng.uniform(1e3, 5e3, n),
        downlink_rate=rng.uniform(5e3, 2e4, n),
        compute_latency=rng.uniform(1.0, 5.0, n),
        num_samples=rng.integers(10, 50, n).astype(float),
        label_coverage=rng.uniform(0.5, 1.0, n),
        train_loss=np.ones(n))


def _ltf(p, idx, key):
    """Deterministic pseudo-training (no dataset needed)."""
    return (jax.tree_util.tree_map(
        lambda x: x * 0.99 + 0.01 * jax.random.normal(key, x.shape), p),
        1.0 / (idx + 1.0))


def _trees_equal(a, b):
    return all(bool(jnp.all(x == y)) for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


# The grouped engine is one fused jitted program; the reference loop runs
# per-client eager ops.  They cannot be one compiled program, and under
# jax 0.9 XLA:CPU sums the Eq. (4) terms in a different order in the two
# (a 1-ulp difference in the aggregate, compounding over rounds), so
# engine-vs-loop learning state is held to a few float32 ulps at O(1)
# scale — the tolerance the dense-baseline parity below already used.
LOOP_RTOL, LOOP_ATOL = 1e-5, 1e-7


def _assert_trees_close(a, b, rtol=LOOP_RTOL, atol=LOOP_ATOL):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


# --- group metadata ----------------------------------------------------------

def test_group_by_shape_partition():
    from repro.fl.heterogeneity import group_by_shape, shape_signature

    _, clients = _ragged_fleet(7)          # widths 12,8,6,12,8,6,12
    groups = group_by_shape(clients)
    assert [g.indices for g in groups] == [(0, 3, 6), (1, 4), (2, 5)]
    assert [g.size for g in groups] == [3, 2, 2]
    # signature identifies shape classes exactly
    assert shape_signature(clients[0]) == shape_signature(clients[3])
    assert shape_signature(clients[0]) != shape_signature(clients[1])
    # homogeneous fleet: one group
    assert len(group_by_shape([clients[0]] * 4)) == 1


# --- step-level bit-exactness ------------------------------------------------

def _pad_to(p, g):
    return jax.tree_util.tree_map(
        lambda pl, gl: pl if pl.shape == gl.shape else jnp.pad(
            pl, [(0, gs - ps) for ps, gs in zip(pl.shape, gl.shape)]),
        p, g)


def _pad_mask_to(m, p, g):
    def _pad(ml, pl, gl):
        full = jnp.broadcast_to(ml, pl.shape)
        if pl.shape == gl.shape:
            return full
        return jnp.pad(full, [(0, gs - ps)
                              for ps, gs in zip(pl.shape, gl.shape)])
    return jax.tree_util.tree_map(_pad, m, p, g)


@pytest.mark.parametrize("full_round", [False, True])
def test_grouped_step_bit_identical_to_padded_loop(full_round):
    """One grouped step == build_masks-with-coverage + zero-pad + Eq. (4)
    stack + Eq. (5)/(6), client by client (exactly what the reference loop
    executor does for a ragged fleet)."""
    from repro.fl.heterogeneity import group_by_shape

    n = 6
    gp, olds = _ragged_fleet(n, seed=3)
    rk = jax.random.PRNGKey(11)
    news = [jax.tree_util.tree_map(
        lambda x, i=i: x + 0.1 * jax.random.normal(
            jax.random.fold_in(jax.random.PRNGKey(50), i), x.shape), p)
        for i, p in enumerate(olds)]
    drop = np.linspace(0.0, 0.75, n)
    weights = np.arange(1.0, n + 1.0)
    cfg = SelectionConfig()

    full_w = cov_mod.channel_widths(gp)
    cr = cov_mod.coverage_rates(
        [cov_mod.channel_widths(p) for p in olds], full_w)

    # --- per-client reference (loop-executor maths)
    masks, dens = [], []
    for i in range(n):
        cov = cov_mod.coverage_pytree(olds[i], cr)
        m = selection.build_masks(
            olds[i], news[i], jnp.asarray(drop[i], jnp.float32), config=cfg,
            coverage=cov, rng=jax.random.fold_in(rk, 10_000 + i))
        masks.append(m)
        dens.append(float(selection.mask_density(news[i], m)))
    agg = aggregation.aggregate_sparse(
        [_pad_to(news[i], gp) for i in range(n)],
        [_pad_mask_to(masks[i], news[i], gp) for i in range(n)],
        weights, prev_global=gp)
    updates = []
    for i in range(n):
        g_local = jax.tree_util.tree_map(
            lambda g, l: g if g.shape == l.shape
            else g[tuple(slice(0, s) for s in l.shape)], agg, news[i])
        if full_round:
            updates.append(g_local)
        else:
            updates.append(aggregation.client_update_sparse(
                g_local, news[i], masks[i]))

    # --- grouped engine
    groups = group_by_shape(olds)
    batches = [GroupBatch(
        indices=jnp.asarray(g.indices, jnp.int32),
        stacked_old=stack_pytrees([olds[i] for i in g.indices]),
        stacked_new=stack_pytrees([news[i] for i in g.indices]),
        coverage=cov_mod.coverage_pytree(olds[g.indices[0]], cr),
        dropout=jnp.asarray(drop[list(g.indices)], jnp.float32))
        for g in groups]
    out = GroupedRoundEngine(cfg).step(batches, gp, weights, rk,
                                       full_round=full_round)

    _assert_trees_close(agg, out.global_params)
    got_dens = np.asarray(out.densities)
    for g, stacked in zip(groups, out.group_client_params):
        for pos, i in enumerate(g.indices):
            upd = jax.tree_util.tree_map(lambda l, pos=pos: l[pos], stacked)
            _assert_trees_close(updates[i], upd)
            assert got_dens[i] == pytest.approx(dens[i], abs=1e-6)


def test_build_masks_batched_coverage_matches_per_client():
    """Eq. (21) coverage division in the batched builder is bit-identical
    to looping build_masks with the same (shared) coverage slice."""
    n = 4
    key = jax.random.PRNGKey(9)
    olds = [_sub_params(jax.random.fold_in(key, i), 8) for i in range(n)]
    news = [jax.tree_util.tree_map(
        lambda x, i=i: x + 0.05 * jax.random.normal(
            jax.random.fold_in(key, 100 + i), x.shape), p)
        for i, p in enumerate(olds)]
    cov = jax.tree_util.tree_map(
        lambda l: jnp.linspace(0.2, 1.0, l.shape[-1]), olds[0])
    drop = np.linspace(0.1, 0.7, n)
    rk = jax.random.PRNGKey(2)
    ids = np.asarray([3, 7, 11, 12])       # non-contiguous fleet positions
    batched, _ = selection.build_masks_batched(
        stack_pytrees(olds), stack_pytrees(news),
        jnp.asarray(drop, jnp.float32), config=SelectionConfig(), rng=rk,
        coverage=cov, client_indices=ids)
    for pos, i in enumerate(ids):
        ref = selection.build_masks(
            olds[pos], news[pos], jnp.asarray(drop[pos], jnp.float32),
            config=SelectionConfig(), coverage=cov,
            rng=jax.random.fold_in(rk, 10_000 + int(i)))
        got = jax.tree_util.tree_map(lambda l: l[pos], batched)
        assert _trees_equal(ref, got)


def test_aggregate_sparse_grouped_single_canvas_matches_sequential():
    """The fused single-scatter canvas (all groups padded + concatenated,
    ONE .at[rows].set per leaf) is bit-identical to the sequential
    per-group scatter path it replaced — including zero-weight rows and
    rows no group owns (prev_global fill)."""
    from repro.fl.heterogeneity import group_by_shape

    n = 7                     # one more row than clients: an un-owned row
    gp, clients = _ragged_fleet(6, seed=11)
    news = [jax.tree_util.tree_map(
        lambda x, i=i: x + 0.1 * jax.random.normal(
            jax.random.fold_in(jax.random.PRNGKey(3), i), x.shape), p)
        for i, p in enumerate(clients)]
    groups = group_by_shape(clients)
    rk = jax.random.PRNGKey(5)
    drop = np.linspace(0.0, 0.7, 6)
    group_params, group_masks, group_idx = [], [], []
    for g in groups:
        stacked_old = stack_pytrees([clients[i] for i in g.indices])
        stacked_new = stack_pytrees([news[i] for i in g.indices])
        masks, _ = selection.build_masks_batched(
            stacked_old, stacked_new,
            jnp.asarray(drop[list(g.indices)], jnp.float32),
            config=SelectionConfig(), rng=rk,
            client_indices=jnp.asarray(g.indices, jnp.int32))
        group_params.append(stacked_new)
        group_masks.append(masks)
        group_idx.append(jnp.asarray(g.indices, jnp.int32))
    weights = np.asarray([1.0, 2.0, 0.0, 3.0, 1.5, 2.5, 4.0])  # 0-weight row
    kw = dict(global_template=gp, prev_global=gp)
    fused = aggregation.aggregate_sparse_grouped(
        group_params, group_masks, group_idx, weights, **kw)
    seq = aggregation.aggregate_sparse_grouped(
        group_params, group_masks, group_idx, weights,
        single_canvas=False, **kw)
    assert _trees_equal(fused, seq)


# --- end-to-end protocol parity ---------------------------------------------

def test_run_scheme_grouped_bit_identical_to_loop():
    """Algorithm 1 on a ragged 3-width fleet: grouped engine vs reference
    loop over several rounds including an h-period full broadcast —
    identical globals, client states, and history."""
    from repro.core import FedDDServer, ProtocolConfig

    n = 6
    gp, clients = _ragged_fleet(n)
    tel = _tel_for(clients)
    kw = dict(scheme="feddd", rounds=4, a_server=0.6, h=3, seed=0)

    s_loop = FedDDServer(gp, ProtocolConfig(batched=False, **kw), tel,
                         client_params=clients)
    assert s_loop.heterogeneous
    r_loop = s_loop.run(_ltf)
    s_grp = FedDDServer(gp, ProtocolConfig(batched=True, **kw), tel,
                        client_params=clients)
    assert s_grp.executor_kind == "grouped"
    r_grp = s_grp.run(_ltf)

    _assert_trees_close(r_loop.global_params, r_grp.global_params)
    for a, b in zip(s_loop.clients, s_grp.clients):
        _assert_trees_close(a.params, b.params)
    for rl, rb in zip(r_loop.history, r_grp.history):
        assert rl.mean_loss == pytest.approx(rb.mean_loss, abs=1e-9)
        assert rl.uploaded_fraction == pytest.approx(rb.uploaded_fraction,
                                                     abs=1e-6)
        np.testing.assert_allclose(rl.dropout_rates, rb.dropout_rates,
                                   atol=1e-12)
        assert rl.participants == rb.participants
        assert rl.sim_time == rb.sim_time


@pytest.mark.parametrize("scheme", ["fedavg", "fedcs", "oort"])
def test_grouped_baselines_match_loop(scheme):
    """Dense baselines on a ragged fleet ride the grouped step (all-ones
    masks, non-participation as 0-weights): history identical, params equal
    to float tolerance (summation order differs)."""
    n = 6
    gp, clients = _ragged_fleet(n, seed=5)
    tel = _tel_for(clients, seed=1)
    kw = dict(rounds=3, a_server=0.6, h=2, seed=0)
    loop = run_scheme(scheme, gp, tel, _ltf, None, client_params=clients,
                      batched=False, **kw)
    grp = run_scheme(scheme, gp, tel, _ltf, None, client_params=clients,
                     batched=True, **kw)
    for x, y in zip(jax.tree_util.tree_leaves(loop.global_params),
                    jax.tree_util.tree_leaves(grp.global_params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-7)
    for rl, rb in zip(loop.history, grp.history):
        assert rl.participants == rb.participants
        assert rl.sim_time == rb.sim_time
        assert rl.uploaded_fraction == pytest.approx(rb.uploaded_fraction,
                                                     abs=1e-9)
        assert rl.mean_loss == pytest.approx(rb.mean_loss, abs=1e-9)


# --- sim runner: ragged fleets -----------------------------------------------

def test_sim_sync_static_ragged_reproduces_protocol_exactly():
    """The grouped engine inside the event-driven runner: sync over a
    static network == the closed-form driver, bit for bit, on a ragged
    fleet (the combined contract of test_sim + this module).  Both
    drivers run the grouped engine's compiled step, so the comparison is
    exact; the engine-vs-loop contract is test_run_scheme_grouped_*."""
    from repro.sim import SimConfig, run_sim

    n = 6
    gp, clients = _ragged_fleet(n)
    tel = _tel_for(clients)
    kw = dict(rounds=5, a_server=0.6, h=3, seed=0)
    ref = run_scheme("feddd", gp, tel, _ltf, None, client_params=clients,
                     batched=True, **kw)
    got = run_sim("feddd", gp, tel, _ltf, None,
                  sim=SimConfig(policy="sync"), client_params=clients, **kw)
    for rr, rg in zip(ref.history, got.history):
        assert rr.sim_time == rg.sim_time          # exact, not approx
        assert rr.uploaded_fraction == pytest.approx(rg.uploaded_fraction,
                                                     abs=1e-6)
        np.testing.assert_array_equal(rr.dropout_rates, rg.dropout_rates)
    assert _trees_equal(ref.global_params, got.global_params)


def test_sim_deadline_and_async_accept_ragged_fleet():
    """Stragglers x ragged fleets: the paper's hardest combined setting
    runs the fast path under every policy."""
    from repro.sim import SimConfig, TraceNetwork, run_sim

    n = 6
    gp, clients = _ragged_fleet(n, seed=7)
    tel = _tel_for(clients, seed=3)
    kw = dict(rounds=4, a_server=0.6, h=3, seed=0)

    # client 0's uplink collapses -> the deadline policy drops it
    epochs = 10
    up = np.tile(tel.uplink_rate, (epochs, 1))
    up[1:, 0] /= 200.0
    net = TraceNetwork(up, np.tile(tel.downlink_rate, (epochs, 1)),
                       np.tile(tel.compute_latency, (epochs, 1)))
    dl = run_sim("feddd", gp, tel, _ltf, None,
                 sim=SimConfig(policy="deadline"), network=net,
                 client_params=clients, **kw)
    assert any(r.participants < n for r in dl.history)
    assert all(r.participants >= 1 for r in dl.history)

    As = run_sim("feddd", gp, tel, _ltf, None, sim=SimConfig(policy="async"),
                 client_params=clients, **kw)
    from repro.sim import AsyncPolicy
    k = AsyncPolicy().resolved_buffer(n)
    assert all(r.participants == k for r in As.history)
    times = [r.sim_time for r in As.history]
    assert all(b > a for a, b in zip(times, times[1:]))


# --- determinism across processes --------------------------------------------

_DIGEST_SNIPPET = r"""
import hashlib
import numpy as np
import jax, jax.numpy as jnp
from repro.core.allocation import ClientTelemetry
from repro.sim import MarkovFadingNetwork, SimConfig, run_sim

WIDTHS = (12, 8, 6)

def sub(key, w):
    k1, k2 = jax.random.split(key)
    return {"fc0": {"w": jax.random.normal(k1, (20, w)), "b": jnp.zeros(w)},
            "fc1": {"w": jax.random.normal(k2, (w, 5)), "b": jnp.zeros(5)}}

def fleet(n=6):
    gp = sub(jax.random.PRNGKey(0), max(WIDTHS))
    return gp, [sub(jax.random.PRNGKey(100 + i), WIDTHS[i % 3])
                for i in range(n)]

def tel(clients):
    n = len(clients)
    rng = np.random.default_rng(0)
    nbytes = [float(sum(l.size * l.dtype.itemsize
                        for l in jax.tree_util.tree_leaves(p)))
              for p in clients]
    return ClientTelemetry(
        model_bytes=np.asarray(nbytes),
        uplink_rate=rng.uniform(1e3, 5e3, n),
        downlink_rate=rng.uniform(5e3, 2e4, n),
        compute_latency=rng.uniform(1.0, 5.0, n),
        num_samples=rng.integers(10, 50, n).astype(float),
        label_coverage=rng.uniform(0.5, 1.0, n),
        train_loss=np.ones(n))

def ltf(p, idx, key):
    return (jax.tree_util.tree_map(
        lambda x: x * 0.99 + 0.01 * jax.random.normal(key, x.shape), p),
        1.0 / (idx + 1.0))

h = hashlib.sha256()
for policy in ("sync", "deadline", "async"):
    gp, clients = fleet()
    t = tel(clients)
    net = MarkovFadingNetwork(t, p_fade=0.3, p_recover=0.4,
                              fade_factor=0.05, seed=7)
    res = run_sim("feddd", gp, t, ltf, None,
                  sim=SimConfig(policy=policy), network=net,
                  client_params=clients, rounds=3, a_server=0.6, h=2, seed=0)
    times = np.asarray([e[0] for e in res.event_trace])
    h.update(times.tobytes())
    h.update(",".join(f"{e[1]}:{e[2]}" for e in res.event_trace).encode())
    h.update(np.asarray([r.sim_time for r in res.history]).tobytes())
    for leaf in jax.tree_util.tree_leaves(res.global_params):
        h.update(np.asarray(leaf).tobytes())
print(h.hexdigest())
"""


def test_grouped_determinism_across_processes():
    """Same seed => identical event order, sim times, and final params in
    independent processes — ragged fleet, all three policies, fading
    network (the grouped-engine analogue of test_sim's digest)."""
    src = str(Path(__file__).resolve().parents[1] / "src")
    digests = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", _DIGEST_SNIPPET],
            capture_output=True, text=True,
            env={"PYTHONPATH": src, "PATH": "/usr/bin:/bin",
                 "JAX_PLATFORMS": "cpu", "HOME": "/tmp"},
            check=False)
        assert out.returncode == 0, out.stderr[-2000:]
        digests.append(out.stdout.strip())
    assert digests[0] == digests[1]
    assert len(digests[0]) == 64
