"""Batched round engine vs the per-client loop: bit-identical results.

The batched engine (core/round_engine.py) is the homogeneous FedDD hot
path; these tests pin its contract: for a fixed seed it produces exactly
the masks, aggregates, client updates, and history the per-client loop
produces — plus the lax.top_k / argsort tie-handling equivalence the mask
builder relies on.  Where the scan body and the per-round dispatches are
two compiled programs that XLA may reduce in different orders, the
contract is a few float32 ulps (:func:`_assert_trees_close`).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregation, run_scheme, selection
from repro.core.round_engine import (BatchedRoundEngine, stack_pytrees,
                                     unstack_pytree)
from repro.core.selection import SelectionConfig

pytestmark = pytest.mark.flcore


def _client_params(key, n, scale=1.0):
    def one(k):
        k1, k2 = jax.random.split(k)
        return {
            "fc0": {"w": scale * jax.random.normal(k1, (20, 12)),
                    "b": jnp.zeros(12)},
            "fc1": {"w": scale * jax.random.normal(k2, (12, 5)),
                    "b": jnp.zeros(5)},
        }
    return [one(jax.random.fold_in(key, i)) for i in range(n)]


def _perturb(params, key, eps=0.1):
    return [jax.tree_util.tree_map(
        lambda x: x + eps * jax.random.normal(jax.random.fold_in(key, i),
                                              x.shape), p)
        for i, p in enumerate(params)]


def _trees_equal(a, b):
    return all(bool(jnp.all(x == y)) for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


def _assert_trees_close(a, b, rtol=1e-5, atol=1e-7):
    """Float32 agreement of two differently compiled programs (a scan
    body vs separate dispatches): a few ulps at the parameters' O(1)
    scale.  Bit equality between them is not a property of the engine:
    XLA:CPU's float32 reduction order differs between the programs.  An
    engine fault — a wrong mask, weight or client — moves values by 1e-2
    and more."""
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


@pytest.mark.parametrize("scheme", selection.SCHEMES)
@pytest.mark.parametrize("full_round", [False, True])
def test_engine_step_bit_identical_to_loop(scheme, full_round):
    """Masks, Eq.(4) aggregate, and Eq.(5)/(6) updates match the loop
    exactly (same seed, same dropout rates)."""
    n = 6
    key = jax.random.PRNGKey(0)
    olds = _client_params(key, n)
    news = _perturb(olds, jax.random.fold_in(key, 1))
    global_params = _client_params(jax.random.fold_in(key, 2), 1)[0]
    drop = np.random.default_rng(0).uniform(0.0, 0.8, n)
    weights = np.arange(1.0, n + 1.0)
    rk = jax.random.PRNGKey(7)
    cfg = SelectionConfig(scheme=scheme)

    # --- per-client loop reference (exactly what FedDDServer.run does)
    masks, dens = [], []
    for i in range(n):
        m = selection.build_masks(
            olds[i], news[i], jnp.asarray(drop[i], jnp.float32), config=cfg,
            rng=jax.random.fold_in(rk, 10_000 + i))
        masks.append(m)
        dens.append(float(selection.mask_density(news[i], m)))
    agg = aggregation.aggregate_sparse(news, masks, weights,
                                       prev_global=global_params)
    if full_round:
        updates = [agg] * n
    else:
        updates = [aggregation.client_update_sparse(agg, news[i], masks[i])
                   for i in range(n)]

    # --- batched engine
    out = BatchedRoundEngine(cfg).step(
        stack_pytrees(olds), stack_pytrees(news), global_params, drop,
        weights, rk, full_round=full_round)

    assert _trees_equal(agg, out.global_params)
    for i, upd in enumerate(unstack_pytree(out.client_params, n)):
        assert _trees_equal(updates[i], upd), f"client {i}"
    np.testing.assert_allclose(np.asarray(out.densities), dens, atol=1e-6)


def test_build_masks_batched_matches_loop_masks():
    n = 5
    key = jax.random.PRNGKey(3)
    olds = _client_params(key, n)
    news = _perturb(olds, jax.random.fold_in(key, 9))
    drop = np.linspace(0.0, 0.75, n)
    rk = jax.random.PRNGKey(11)
    cfg = SelectionConfig()
    batched, _ = selection.build_masks_batched(
        stack_pytrees(olds), stack_pytrees(news),
        jnp.asarray(drop, jnp.float32), config=cfg, rng=rk)
    for i in range(n):
        ref = selection.build_masks(
            olds[i], news[i], jnp.asarray(drop[i], jnp.float32), config=cfg,
            rng=jax.random.fold_in(rk, 10_000 + i))
        got = jax.tree_util.tree_map(lambda l: l[i], batched)
        for (path, a), (_, b) in zip(
                jax.tree_util.tree_flatten_with_path(ref)[0],
                jax.tree_util.tree_flatten_with_path(got)[0]):
            assert a.shape == b.shape
            assert bool(jnp.all(a == b)), jax.tree_util.keystr(path)


def test_aggregate_sparse_stacked_matches_list_path():
    n = 4
    key = jax.random.PRNGKey(5)
    news = _client_params(key, n)
    masks = [selection.build_masks(p, p, jnp.asarray(0.5),
                                   config=SelectionConfig(scheme="ordered"))
             for p in news]
    prev = _client_params(jax.random.fold_in(key, 1), 1)[0]
    wts = [1.0, 2.0, 0.5, 3.0]
    a = aggregation.aggregate_sparse(news, masks, wts, prev_global=prev)
    b = aggregation.aggregate_sparse_stacked(
        stack_pytrees(news), stack_pytrees(masks), wts, prev_global=prev)
    assert _trees_equal(a, b)


def test_run_scheme_batched_bit_identical_to_loop():
    """End-to-end Algorithm 1: batched vs loop over several rounds,
    including a full-broadcast (h) round — identical history + globals."""
    from repro.core.allocation import ClientTelemetry

    n = 6
    rng = np.random.default_rng(0)
    params = _client_params(jax.random.PRNGKey(0), 1)[0]
    nbytes = float(sum(l.size * l.dtype.itemsize
                       for l in jax.tree_util.tree_leaves(params)))
    tel = ClientTelemetry(
        model_bytes=np.full(n, nbytes),
        uplink_rate=rng.uniform(1e3, 5e3, n),
        downlink_rate=rng.uniform(5e3, 2e4, n),
        compute_latency=rng.uniform(1.0, 5.0, n),
        num_samples=rng.integers(10, 50, n).astype(float),
        label_coverage=rng.uniform(0.5, 1.0, n),
        train_loss=np.ones(n))

    def ltf(p, idx, key):
        # deterministic pseudo-training: same fn both paths
        return (jax.tree_util.tree_map(
            lambda x: x * 0.99 + 0.01 * jax.random.normal(key, x.shape), p),
            1.0 / (idx + 1.0))

    kw = dict(rounds=4, a_server=0.6, h=3, seed=0)
    loop = run_scheme("feddd", params, tel, ltf, None, batched=False, **kw)
    bat = run_scheme("feddd", params, tel, ltf, None, batched=True, **kw)
    assert _trees_equal(loop.global_params, bat.global_params)
    for rl, rb in zip(loop.history, bat.history):
        assert rl.uploaded_fraction == pytest.approx(rb.uploaded_fraction,
                                                     abs=1e-6)
        assert rl.mean_loss == pytest.approx(rb.mean_loss, abs=1e-9)
        np.testing.assert_allclose(rl.dropout_rates, rb.dropout_rates,
                                   atol=1e-12)
        assert rl.participants == rb.participants


@pytest.mark.parametrize("scheme", ["fedavg", "fedcs", "oort"])
def test_baselines_batched_engine_matches_loop(scheme):
    """Baselines ride the fused engine step too (dense all-ones masks,
    non-participation as a 0 aggregation weight): history identical to the
    per-client loop, params equal to float tolerance (summation order)."""
    from repro.core.allocation import ClientTelemetry

    n = 6
    rng = np.random.default_rng(0)
    params = _client_params(jax.random.PRNGKey(0), 1)[0]
    nbytes = float(sum(l.size * l.dtype.itemsize
                       for l in jax.tree_util.tree_leaves(params)))
    tel = ClientTelemetry(
        model_bytes=np.full(n, nbytes),
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

    kw = dict(rounds=4, a_server=0.6, h=3, seed=0)
    loop = run_scheme(scheme, params, tel, ltf, None, batched=False, **kw)
    bat = run_scheme(scheme, params, tel, ltf, None, batched=True, **kw)
    for x, y in zip(jax.tree_util.tree_leaves(loop.global_params),
                    jax.tree_util.tree_leaves(bat.global_params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-7)
    for rl, rb in zip(loop.history, bat.history):
        assert rl.participants == rb.participants
        assert rl.sim_time == rb.sim_time
        assert rl.uploaded_fraction == pytest.approx(rb.uploaded_fraction,
                                                     abs=1e-9)
        assert rl.mean_loss == pytest.approx(rb.mean_loss, abs=1e-9)


def test_batched_train_fn_fuses_training():
    """batched_train_fn path == per-client python training (same maths)."""
    from repro.core import FedDDServer, ProtocolConfig
    from repro.core.allocation import ClientTelemetry

    n = 4
    params = _client_params(jax.random.PRNGKey(2), 1)[0]
    nbytes = float(sum(l.size * l.dtype.itemsize
                       for l in jax.tree_util.tree_leaves(params)))
    rng = np.random.default_rng(1)
    tel = ClientTelemetry(
        model_bytes=np.full(n, nbytes),
        uplink_rate=rng.uniform(1e3, 5e3, n),
        downlink_rate=rng.uniform(5e3, 2e4, n),
        compute_latency=rng.uniform(1.0, 5.0, n),
        num_samples=np.full(n, 10.0),
        label_coverage=np.ones(n),
        train_loss=np.ones(n))

    def per_client(p, idx, key):
        del key
        return jax.tree_util.tree_map(lambda x: 0.9 * x, p), 0.5

    def batched(stacked, key):
        del key
        return (jax.tree_util.tree_map(lambda x: 0.9 * x, stacked),
                jnp.full((n,), 0.5))

    kw = dict(scheme="feddd", rounds=3, a_server=0.6, h=2, seed=0)
    s1 = FedDDServer(params, ProtocolConfig(**kw), tel)
    r1 = s1.run(per_client)
    s2 = FedDDServer(params, ProtocolConfig(**kw), tel)
    r2 = s2.run(batched_train_fn=batched)
    assert _trees_equal(r1.global_params, r2.global_params)
    # stacked client state synced back into ClientState
    assert _trees_equal(s1.clients[0].params, s2.clients[0].params)


@pytest.mark.parametrize("scheme", ["fedavg", "fedcs", "oort"])
def test_batched_train_fn_baselines_respect_participation(scheme):
    """Dense-baseline runs may fuse training too, but non-participants must
    not train: their params stay stale (out of the aggregate) and their
    losses stay stale in the server's view — identical to the per-client
    engine trainer that simply skips them."""
    from repro.core import FedDDServer, ProtocolConfig
    from repro.core.allocation import ClientTelemetry

    n = 6
    params = _client_params(jax.random.PRNGKey(4), 1)[0]
    nbytes = float(sum(l.size * l.dtype.itemsize
                       for l in jax.tree_util.tree_leaves(params)))
    rng = np.random.default_rng(2)
    tel = ClientTelemetry(
        model_bytes=np.full(n, nbytes),
        uplink_rate=rng.uniform(1e3, 5e3, n),
        downlink_rate=rng.uniform(5e3, 2e4, n),
        compute_latency=rng.uniform(1.0, 5.0, n),
        num_samples=rng.integers(10, 50, n).astype(float),
        label_coverage=rng.uniform(0.5, 1.0, n),
        train_loss=np.ones(n))

    def per_client(p, idx, key):
        del key
        return jax.tree_util.tree_map(lambda x: 0.9 * x, p), 0.25

    def batched(stacked, key):
        del key
        return (jax.tree_util.tree_map(lambda x: 0.9 * x, stacked),
                jnp.full((n,), 0.25))

    kw = dict(scheme=scheme, rounds=3, a_server=0.5, h=2, seed=0)
    s1 = FedDDServer(params, ProtocolConfig(**kw), tel)
    r1 = s1.run(per_client)
    s2 = FedDDServer(params, ProtocolConfig(**kw), tel)
    r2 = s2.run(batched_train_fn=batched)
    assert _trees_equal(r1.global_params, r2.global_params)
    for a, b in zip(s1.clients, s2.clients):
        assert _trees_equal(a.params, b.params)
    for ra, rb in zip(r1.history, r2.history):
        assert ra.participants == rb.participants
        assert ra.mean_loss == pytest.approx(rb.mean_loss, abs=1e-9)
        assert ra.uploaded_fraction == pytest.approx(rb.uploaded_fraction,
                                                     abs=1e-9)
    # sanity: the scenario exercises actual non-participation
    assert any(r.participants < n for r in r1.history) or scheme == "fedavg"


def test_batched_train_fn_rejected_off_engine_path():
    from repro.core import FedDDServer, ProtocolConfig
    from repro.core.allocation import ClientTelemetry

    n = 2
    params = {"w": jnp.ones((4, 4))}
    tel = ClientTelemetry(*[np.ones(n)] * 7)
    server = FedDDServer(params, ProtocolConfig(scheme="feddd",
                                                batched=False), tel)
    with pytest.raises(ValueError, match="batched_train_fn"):
        server.run(batched_train_fn=lambda s, k: (s, jnp.zeros(n)))


# --- multi-round scanned dispatch (rounds_per_dispatch > 1) ----------------

def _scan_telemetry(n, nbytes, seed=0):
    from repro.core.allocation import ClientTelemetry

    rng = np.random.default_rng(seed)
    return ClientTelemetry(
        model_bytes=np.full(n, nbytes),
        uplink_rate=rng.uniform(1e3, 5e3, n),
        downlink_rate=rng.uniform(5e3, 2e4, n),
        compute_latency=rng.uniform(1.0, 5.0, n),
        num_samples=rng.integers(10, 50, n).astype(float),
        label_coverage=rng.uniform(0.5, 1.0, n),
        train_loss=np.ones(n))


def _make_scan_fixture(n=8, seed=0):
    params = _client_params(jax.random.PRNGKey(seed), 1)[0]
    nbytes = float(sum(l.size * l.dtype.itemsize
                       for l in jax.tree_util.tree_leaves(params)))
    tel = _scan_telemetry(n, nbytes, seed=seed)

    # jitted so the sequential path runs the same XLA-compiled arithmetic
    # the scan inlines (an eager fn can differ in the last f32 bit: fma)
    @jax.jit
    def batched(stacked, key):
        new = jax.tree_util.tree_map(
            lambda x: x * 0.99 + 0.01 * jax.random.normal(
                jax.random.fold_in(key, 1), x.shape), stacked)
        l0 = jax.tree_util.tree_leaves(new)[0]
        losses = jnp.mean(jnp.abs(l0.reshape(l0.shape[0], -1)), axis=1)
        return new, losses

    return params, tel, batched


def _assert_histories_identical(h_seq, h_scan, loss_rel=0.0):
    """Learning state must match EXACTLY (``loss_rel=0``); the
    allocator-derived fields (dropout rates and the Eq. (12) clock
    computed from them) are held to float32-ulp scale — XLA compiles the
    fenced golden-section search per program, so its last bit is context
    sensitive (it matches exactly on this fixture today, but a jax/XLA
    bump may legally flip an ulp)."""
    assert len(h_seq) == len(h_scan)
    for ra, rb in zip(h_seq, h_scan):
        assert ra.round == rb.round
        assert ra.mean_loss == pytest.approx(rb.mean_loss, rel=loss_rel,
                                             abs=0.0)
        assert ra.uploaded_fraction == rb.uploaded_fraction   # exact
        assert ra.participants == rb.participants
        np.testing.assert_allclose(ra.dropout_rates, rb.dropout_rates,
                                   rtol=0, atol=5e-7)
        assert rb.sim_time == pytest.approx(ra.sim_time, rel=1e-6)
        assert rb.sim_round_time == pytest.approx(ra.sim_round_time,
                                                  rel=1e-6)


@pytest.mark.parametrize("scheme", ["feddd", "fedavg", "fedcs", "oort"])
def test_rounds_per_dispatch_bit_identical_to_sequential(scheme):
    """K scanned rounds == K per-round engine dispatches, bit for bit:
    global params, client params, losses, dropout rates, and Eq. (12)
    times (feddd runs the in-scan allocator + clock; the dense baselines
    run full uploads with fedcs static / oort traced selection).  rounds=7
    with K=4 also exercises the partial trailing chunk."""
    from repro.core import FedDDServer, ProtocolConfig

    params, tel, batched = _make_scan_fixture()
    kw = dict(scheme=scheme, rounds=7, a_server=0.6, h=3, seed=0,
              allocator="jax")
    s_seq = FedDDServer(params, ProtocolConfig(**kw), tel)
    r_seq = s_seq.run(batched_train_fn=batched)
    s_scan = FedDDServer(params, ProtocolConfig(rounds_per_dispatch=4,
                                                **kw), tel)
    r_scan = s_scan.run(batched_train_fn=batched)

    if scheme == "feddd":
        assert _trees_equal(r_seq.global_params, r_scan.global_params)
        for a, b in zip(s_seq.clients, s_scan.clients):
            assert _trees_equal(a.params, b.params)
        _assert_histories_identical(r_seq.history, r_scan.history)
    else:
        # The dense baselines cannot run one compiled program on both
        # sides: the scan body fuses the trainer, the participation
        # select and the Eq. (4) sum into one XLA program, while the
        # sequential side dispatches the jitted trainer and the engine
        # step separately.  Under jax 0.9 XLA:CPU reduces the trainer's
        # loss in a different order in the two programs (one participant
        # loses 1 ulp in round 1), so the learning state agrees to a few
        # float32 ulps (eps 1.2e-7) over 7 rounds, not bit for bit.
        _assert_trees_close(r_seq.global_params, r_scan.global_params)
        for a, b in zip(s_seq.clients, s_scan.clients):
            _assert_trees_close(a.params, b.params)
        _assert_histories_identical(r_seq.history, r_scan.history,
                                    loss_rel=1e-5)
    # the scenario actually exercises selection for the budgeted baselines
    if scheme in ("fedcs", "oort"):
        assert any(r.participants < tel.num_clients
                   for r in r_seq.history)


def test_rounds_per_dispatch_chunk_boundaries_agree():
    """Chunk size must not leak into results: K=2, K=3 (uneven chunks),
    and K=rounds all reproduce the K=1 stream."""
    from repro.core import FedDDServer, ProtocolConfig

    params, tel, batched = _make_scan_fixture(seed=3)
    kw = dict(scheme="feddd", rounds=6, a_server=0.6, h=3, seed=0,
              allocator="jax")
    ref = FedDDServer(params, ProtocolConfig(**kw), tel).run(
        batched_train_fn=batched)
    for k in (2, 3, 6):
        got = FedDDServer(params, ProtocolConfig(rounds_per_dispatch=k,
                                                 **kw), tel).run(
            batched_train_fn=batched)
        assert _trees_equal(ref.global_params, got.global_params), k
        _assert_histories_identical(ref.history, got.history)


def test_scanned_engine_run_trace_and_device_clock():
    """Engine-level contract of BatchedRoundEngine.run: trace shapes are
    (K, N), the traced f32 clock tracks the float64 host recompute, and
    the final carry losses/dropout equal the last trace row."""
    from repro.core import baselines
    from repro.core.round_engine import (BatchedRoundEngine, ScanState,
                                         ScanTelemetry, stack_pytrees)

    n, k = 6, 5
    params, tel, batched = _make_scan_fixture(n=n, seed=1)
    engine = BatchedRoundEngine(SelectionConfig())
    state = ScanState(
        client_params=stack_pytrees([params] * n),
        global_params=params,
        losses=jnp.ones((n,), jnp.float32),
        dropout=jnp.zeros((n,), jnp.float32),
        rng=jax.random.PRNGKey(0),
        sim_time=jnp.zeros((), jnp.float32))
    out, trace = engine.run(
        state, ScanTelemetry.from_host(tel), num_rounds=k,
        batched_train_fn=batched, weights=tel.num_samples, h=3,
        a_server=0.6, d_max=0.8, delta=1.0,
        global_model_bytes=float(np.max(tel.model_bytes)))
    assert trace.losses.shape == (k, n)
    assert trace.densities.shape == (k, n)
    assert trace.next_dropout.shape == (k, n)
    assert trace.participants.shape == (k, n)
    assert trace.round_time.shape == (k,)
    assert bool(jnp.all(trace.participants))         # feddd: everyone
    np.testing.assert_array_equal(np.asarray(out.losses),
                                  np.asarray(trace.losses[-1]))
    np.testing.assert_array_equal(np.asarray(out.dropout),
                                  np.asarray(trace.next_dropout[-1]))
    # device f32 clock vs host f64 Eq. (12): close, and cumulative
    d = np.zeros(n)
    expect = []
    for j in range(k):
        expect.append(np.max(baselines.round_times(tel, d)))
        d = np.asarray(trace.next_dropout[j], float)
    np.testing.assert_allclose(np.asarray(trace.round_time), expect,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(trace.sim_time),
                               np.cumsum(expect), rtol=1e-5)


def test_scanned_run_donates_stacked_carry():
    """donate_argnums targets BOTH model-buffer carries: the stacked
    client params AND the global params update in place (no per-dispatch
    copy of either); the tiny losses/rng/clock entries stay un-donated.
    The protocol executor copies the user-provided global pytree once
    before its first chunk, so caller arrays are never invalidated
    (test_rounds_per_dispatch_* cover that side).  XLA implements
    donation on CPU/GPU/TPU for the pinned jax version; if a backend ever
    declines it, it falls back to a copy and jax warns at compile — this
    test would catch the regression by the carries staying live."""
    from repro.core.round_engine import (BatchedRoundEngine, ScanState,
                                         ScanTelemetry, stack_pytrees)

    n = 4
    params, tel, batched = _make_scan_fixture(n=n, seed=2)
    stacked = stack_pytrees([params] * n)
    gparams = jax.tree_util.tree_map(jnp.array, params)
    donated_leaf = jax.tree_util.tree_leaves(stacked)[0]
    global_leaf = jax.tree_util.tree_leaves(gparams)[0]
    losses_in = jnp.ones((n,), jnp.float32)
    engine = BatchedRoundEngine(SelectionConfig())
    state = ScanState(stacked, gparams, losses_in,
                      jnp.zeros((n,), jnp.float32), jax.random.PRNGKey(1),
                      jnp.zeros((), jnp.float32))
    kw = dict(num_rounds=3, batched_train_fn=batched,
              weights=tel.num_samples, h=3, a_server=0.6, d_max=0.8,
              delta=1.0, global_model_bytes=float(np.max(tel.model_bytes)))
    out, _ = engine.run(state, ScanTelemetry.from_host(tel), **kw)
    assert donated_leaf.is_deleted()         # stacked carry consumed
    assert global_leaf.is_deleted()          # global carry consumed too
    assert not losses_in.is_deleted()        # small carries never donated
    # chaining chunks off the returned carry works (each chunk donates
    # the previous chunk's output, which only the caller holds)
    out2, _ = engine.run(out, ScanTelemetry.from_host(tel), **kw)
    jax.block_until_ready(jax.tree_util.tree_leaves(out2.client_params))
    assert jax.tree_util.tree_leaves(out.client_params)[0].is_deleted()
    assert jax.tree_util.tree_leaves(out.global_params)[0].is_deleted()


def test_rounds_per_dispatch_validation():
    """The scanned path's preconditions fail loudly: numpy allocator,
    K < 1, missing batched_train_fn, per-round eval_fn, and non-engine
    routes (heterogeneous fleets, batched=False) are all rejected."""
    from repro.core import FedDDServer, ProtocolConfig
    from repro.core.allocation import ClientTelemetry

    with pytest.raises(ValueError, match="allocator"):
        ProtocolConfig(rounds_per_dispatch=2)
    with pytest.raises(ValueError, match="rounds_per_dispatch"):
        ProtocolConfig(rounds_per_dispatch=0)

    params, tel, batched = _make_scan_fixture(n=4)
    cfg = dict(scheme="feddd", rounds=2, allocator="jax",
               rounds_per_dispatch=2)

    def ltf(p, idx, key):
        return p, 1.0

    srv = FedDDServer(params, ProtocolConfig(**cfg), tel)
    with pytest.raises(ValueError, match="batched_train_fn"):
        srv.run(ltf)
    srv = FedDDServer(params, ProtocolConfig(**cfg), tel)
    with pytest.raises(ValueError, match="eval_fn"):
        srv.run(batched_train_fn=batched, eval_fn=lambda p: {})
    srv = FedDDServer(params, ProtocolConfig(batched=False, **cfg), tel)
    with pytest.raises(ValueError, match="homogeneous"):
        srv.run(batched_train_fn=batched)

    # ragged fleet routes to the grouped engine -> rejected
    ragged = [params] + [jax.tree_util.tree_map(
        lambda l: l[..., :-1] if l.ndim else l, params)] * 3
    n4 = ClientTelemetry(*[np.ones(4)] * 7)
    srv = FedDDServer(params, ProtocolConfig(**cfg), n4,
                      client_params=ragged)
    with pytest.raises(ValueError):
        srv.run(batched_train_fn=batched)


# --- lax.top_k vs argsort tie handling -------------------------------------

def test_mask_from_scores_topk_matches_argsort_on_ties():
    """Both break ties toward the LOWER channel index; masks must be equal
    for every keep count, including duplicate-heavy score vectors."""
    cases = [
        jnp.asarray([1.0, 3.0, 3.0, 2.0, 3.0, 1.0]),
        jnp.zeros(8),
        jnp.asarray([2.0, 2.0, 2.0, 2.0]),
        jnp.asarray([5.0, 4.0, 3.0, 2.0, 1.0]),
        jnp.asarray([1.0, 2.0, 3.0, 4.0, 5.0]),
    ]
    for scores in cases:
        c = scores.shape[0]
        for keep in range(c + 1):
            a = selection.mask_from_scores(scores, keep, c)
            b = selection.mask_from_scores_argsort(scores, keep, c)
            assert bool(jnp.all(a == b)), (scores, keep)
            assert int(a.sum()) == keep


def test_mask_from_scores_tie_prefers_lower_index():
    scores = jnp.asarray([1.0, 7.0, 7.0, 7.0, 0.0])
    m = selection.mask_from_scores(scores, 2, 5)
    np.testing.assert_array_equal(np.asarray(m), [0, 1, 1, 0, 0])


# --- batched kernel wrappers -----------------------------------------------

def test_kernel_batched_importance_matches_per_client():
    from repro.kernels.importance import ops as kops
    key = jax.random.PRNGKey(0)
    wo = jax.random.normal(key, (5, 33, 17))
    wn = wo + 0.2 * jax.random.normal(jax.random.fold_in(key, 1), wo.shape)
    got = kops.channel_importance_batched(wo, wn, channel_axis=-1)
    want = jnp.stack([kops.channel_importance(wo[i], wn[i], channel_axis=-1)
                      for i in range(5)])
    assert got.shape == (5, 17)
    assert bool(jnp.all(got == want))


def test_engine_use_kernel_matches_jnp_path():
    n = 4
    key = jax.random.PRNGKey(8)
    olds = _client_params(key, n)
    news = _perturb(olds, jax.random.fold_in(key, 4))
    g = _client_params(jax.random.fold_in(key, 5), 1)[0]
    drop = np.full(n, 0.5)
    w = np.ones(n)
    rk = jax.random.PRNGKey(0)
    a = BatchedRoundEngine(SelectionConfig(use_kernel=False)).step(
        stack_pytrees(olds), stack_pytrees(news), g, drop, w, rk,
        full_round=False)
    b = BatchedRoundEngine(SelectionConfig(use_kernel=True)).step(
        stack_pytrees(olds), stack_pytrees(news), g, drop, w, rk,
        full_round=False)
    for x, y in zip(jax.tree_util.tree_leaves(a.global_params),
                    jax.tree_util.tree_leaves(b.global_params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=2e-5, atol=1e-6)


# --- sparse_collective satellite fixes -------------------------------------

def test_make_federated_allreduce_forwards_k_local():
    """k_local zero-weights rows beyond each participant's own keep count;
    with a single participant and k_local=1 only the top-1 channel (plus
    untouched positions) can change."""
    from repro.core.sparse_collective import make_federated_allreduce

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("pod",))
    local = jnp.arange(12.0).reshape(6, 2)
    scores = jnp.asarray([0.0, 5.0, 1.0, 4.0, 2.0, 3.0])
    f = make_federated_allreduce(0.5, "pod")   # static buffer k=3

    def body(x, s, kl):
        return f(x, s, 1.0, kl[0])

    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(jax.sharding.PartitionSpec(),) * 3,
        out_specs=jax.sharding.PartitionSpec(),
        check_vma=False)(local, scores, jnp.asarray([1]))
    # rows beyond k_local=1 keep their LOCAL values (weight 0 => uncovered)
    np.testing.assert_allclose(np.asarray(out), np.asarray(local))

    # signature is importable/evaluable (the latent Optional NameError)
    import typing
    from repro.core import sparse_collective
    hints = typing.get_type_hints(sparse_collective.sparse_allgather_mean)
    assert "k_local" in hints


# --- stack / unstack: one compiled dispatch, bit-equal to the eager ops ----

def _eager_stack(trees):
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *trees)


def _eager_unstack(stacked, n):
    return [jax.tree_util.tree_map(lambda l: l[i], stacked)
            for i in range(n)]


def _assert_leaves_identical(a, b):
    """Same tree, and per leaf the same shape, dtype, weak type and bits."""
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        assert (x.shape, x.dtype, x.weak_type) == \
            (y.shape, y.dtype, y.weak_type)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _mixed_dtype_client(key, i):
    k1, k2 = jax.random.split(jax.random.fold_in(key, i))
    return {"enc": {"w": jax.random.normal(k1, (3, 4)),
                    "scale": jax.random.normal(k2, (4,)).astype(
                        jnp.bfloat16)},
            "steps": jnp.arange(5, dtype=jnp.int32) + i}


def _scalar_leaf_client(key, i):
    return {"w": jax.random.normal(jax.random.fold_in(key, i), (2, 3)),
            "loss": jnp.float32(0.5 * i), "step": jnp.int32(i)}


def _toy_width(spec):
    """A Table 3 VGG layer list with every width divided by 8."""
    def cut(c):
        return c if c in (3, 10) else c // 8
    return [l if l[0] == "pool" else (l[0], cut(l[1]), cut(l[2])) + l[3:]
            for l in spec]


def _hetero_a_toy_fleets(key, per_width):
    """One fleet per Table 3 (hetero-a) width, at toy size (leaf shapes
    from ``init_cnn_spec``, values drawn on the host: no compile per
    shape)."""
    from repro.fl.models import HETERO_A_SPECS, init_cnn_spec
    rng = np.random.default_rng(int(key[-1]))
    fleets = []
    for spec in HETERO_A_SPECS:
        shapes = jax.eval_shape(
            lambda k, s=_toy_width(spec): init_cnn_spec(k, s), key)
        fleets.append([jax.tree_util.tree_map(
            lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
            shapes) for _ in range(per_width)])
    return fleets


@pytest.mark.parametrize("fleet,n", [
    ("mixed_dtypes", 1), ("mixed_dtypes", 13),
    ("scalar_leaves", 1), ("scalar_leaves", 13),
    ("vgg_hetero_a_toy", 3)])
def test_stack_unstack_bit_equal_to_eager_and_round_trip(fleet, n):
    """The compiled stack / unstack give the eager ``jnp.stack`` / ``l[i]``
    results bit for bit (values, shapes, dtypes), invert each other, and
    ``unstack_pytree(x, 1)[0]`` of an n-row stack is its first client."""
    key = jax.random.PRNGKey(4)
    if fleet == "vgg_hetero_a_toy":
        fleets = _hetero_a_toy_fleets(key, n)
    else:
        make = {"mixed_dtypes": _mixed_dtype_client,
                "scalar_leaves": _scalar_leaf_client}[fleet]
        fleets = [[make(key, i) for i in range(n)]]
    for trees in fleets:
        stacked = stack_pytrees(trees)
        _assert_leaves_identical(stacked, _eager_stack(trees))
        clients = unstack_pytree(stacked, n)
        assert len(clients) == n
        for got, want, orig in zip(clients, _eager_unstack(stacked, n),
                                   trees):
            _assert_leaves_identical(got, want)
            _assert_leaves_identical(got, orig)
        _assert_leaves_identical(stack_pytrees(clients), stacked)
        _assert_leaves_identical(unstack_pytree(stacked, 1)[0], trees[0])


def test_stack_unstack_second_fleet_of_same_shapes_compiles_nothing():
    """jit keys the two programs on tree, leaf avals and n alone: a second
    fleet of the same shapes (new values) compiles and loads nothing —
    what keeps a benchmark window free of compiles."""
    key = jax.random.PRNGKey(6)
    first = [_mixed_dtype_client(key, i) for i in range(7)]
    second = [_mixed_dtype_client(jax.random.fold_in(key, 1), i)
              for i in range(7)]
    unstack_pytree(stack_pytrees(first), 7)
    jax.block_until_ready(second)
    events = []

    def listen(name, *_a, **_k):
        if name in ("/jax/core/compile/backend_compile_duration",
                    "/jax/compilation_cache/cache_retrieval_time_sec"):
            events.append(name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        clients = unstack_pytree(stack_pytrees(second), 7)
        jax.block_until_ready(clients)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert events == []
    _assert_leaves_identical(clients[6], second[6])
