"""Population-scale serving (repro/population): identity contract,
availability/cohort determinism, sticky client state, and observability.

Pins the subsystem's contracts:

* identity — a population whose size equals the fleet, with always-on
  availability and the default sampler, is BIT-identical to today's
  fleet runs on the batched, grouped, and scanned engine paths (event
  trace, round records, and trained global params all exact);
* determinism — availability draws and cohort samples are pure
  functions of ``(seed, tag, epoch, client)``: prefix/permutation
  invariant per client (hypothesis) and identical across processes
  (subprocess digests, mirroring tests/test_faults.py);
* sampling — every sampler returns exactly ``cohort_size`` sorted ids,
  topping up deterministically when availability leaves the online set
  short, and Oort's exploit slots track the sticky utility;
* state — the store's economy arrays update only for the sampled
  cohort, and ``cold_start="mean"`` swaps never-seen cohort members'
  LP telemetry for population means;
* obs — population runs emit per-round ``cohort`` events and the
  report CLI renders a participation section from them.
"""

import hashlib
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hypothesis_compat import given, settings, st
from repro.core import FedDDServer, ProtocolConfig
from repro.core.allocation import ClientTelemetry
from repro.obs import ObsConfig, read_events
from repro.obs import report as obs_report
from repro.population import (AlwaysOn, BernoulliAvailability,
                              DiurnalAvailability, Population,
                              TraceAvailability, make_availability,
                              make_sampler, uniform_draws)
from repro.population.availability import _TAG_AVAIL
from repro.sim import AsyncPolicy, SimConfig, run_sim

pytestmark = pytest.mark.flcore


# --- shared fixtures ---------------------------------------------------------

def _params(key):
    k1, k2 = jax.random.split(key)
    return {
        "fc0": {"w": jax.random.normal(k1, (20, 12)), "b": jnp.zeros(12)},
        "fc1": {"w": jax.random.normal(k2, (12, 5)), "b": jnp.zeros(5)},
    }


def _sub_params(key, width):
    k1, k2 = jax.random.split(key)
    return {
        "fc0": {"w": jax.random.normal(k1, (20, width)),
                "b": jnp.zeros(width)},
        "fc1": {"w": jax.random.normal(k2, (width, 5)), "b": jnp.zeros(5)},
    }


def _tel(n, seed=0):
    rng = np.random.default_rng(seed)
    nbytes = float(sum(l.size * l.dtype.itemsize
                       for l in jax.tree_util.tree_leaves(
                           _params(jax.random.PRNGKey(0)))))
    return ClientTelemetry(
        model_bytes=np.full(n, nbytes),
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


def _assert_runs_identical(ref, got):
    """Bit-identity: event trace, per-round records, global params."""
    assert ref.event_trace == got.event_trace
    for rr, rg in zip(ref.history, got.history):
        assert rr.sim_time == rg.sim_time
        assert rr.mean_loss == rg.mean_loss
        assert rr.uploaded_bytes == rg.uploaded_bytes
        assert rr.wire_bytes == rg.wire_bytes
        np.testing.assert_array_equal(rr.dropout_rates, rg.dropout_rates)
    assert _trees_equal(ref.global_params, got.global_params)


# --- identity contract: population == fleet, bit for bit ---------------------

def test_identity_contract_batched_bit_exact():
    """population=N + always-on + default sampler + cohort==population
    reproduces today's stacked-fleet sim runs exactly — event trace,
    round records, and trained global params."""
    n = 6
    kw = dict(rounds=5, a_server=0.6, h=3, seed=0,
              sim=SimConfig(policy="sync"))
    ref = run_sim("feddd", _params(jax.random.PRNGKey(0)), _tel(n),
                  _ltf, None, **kw)
    got = run_sim("feddd", _params(jax.random.PRNGKey(0)), _tel(n),
                  _ltf, None, population=Population(_tel(n)), **kw)
    _assert_runs_identical(ref, got)


def test_identity_contract_grouped_bit_exact():
    """Same contract on the grouped (ragged heterogeneous-fleet) path:
    per-client param trees of different widths route through
    _GroupedWaveFleet, and the population store holds each client's
    own-width tree."""
    n = 4
    widths = (12, 8, 12, 6)
    gp = _sub_params(jax.random.PRNGKey(0), 12)
    clients = [_sub_params(jax.random.PRNGKey(100 + i), w)
               for i, w in enumerate(widths)]
    kw = dict(rounds=3, a_server=0.6, h=2, seed=0,
              sim=SimConfig(policy="sync"))
    ref = run_sim("feddd", gp, _tel(n), _ltf, None,
                  client_params=clients, **kw)
    got = run_sim("feddd", gp, _tel(n), _ltf, None,
                  client_params=clients, population=Population(_tel(n)),
                  **kw)
    _assert_runs_identical(ref, got)


def test_identity_contract_scanned_path_bit_exact():
    """Same contract against the scanned driver: with a key-free trainer
    (the same arithmetic whether vmapped inside the lax.scan dispatch or
    run per client in the sim) the population-identity sim reproduces
    FedDDServer's rounds_per_dispatch>1 path — Eq. (12) clock and
    jax-allocator dropout rates exactly, losses and global params to
    float32 ulps.  The two cannot be one compiled program (population
    runs always route through the sim, which does not scan), and under
    jax 0.9 XLA:CPU reduces the trainer's loss inside the scan body in a
    different order than the per-client call (round 1's mean loss moves
    by ~4e-7 relative)."""
    n = 8

    def ltf(p, idx, key):
        new = jax.tree_util.tree_map(lambda x: x * jnp.float32(0.99), p)
        return new, jnp.mean(jnp.abs(new["fc0"]["w"]))

    @jax.jit
    def batched(stacked, key):
        new = jax.tree_util.tree_map(
            lambda x: x * jnp.float32(0.99), stacked)
        w = new["fc0"]["w"]
        return new, jnp.mean(jnp.abs(w), axis=tuple(range(1, w.ndim)))

    kw = dict(scheme="feddd", rounds=7, a_server=0.6, h=3, seed=0,
              allocator="jax")
    scan = FedDDServer(_params(jax.random.PRNGKey(0)),
                       ProtocolConfig(rounds_per_dispatch=4, **kw),
                       _tel(n)).run(batched_train_fn=batched)
    pop = run_sim("feddd", _params(jax.random.PRNGKey(0)), _tel(n),
                  ltf, None, population=Population(_tel(n)),
                  sim=SimConfig(policy="sync"),
                  rounds=7, a_server=0.6, h=3, seed=0, allocator="jax")
    for hs, hp in zip(scan.history, pop.history):
        assert hs.mean_loss == pytest.approx(hp.mean_loss, rel=1e-5)
        assert hs.sim_time == hp.sim_time
        np.testing.assert_array_equal(np.asarray(hs.dropout_rates),
                                      np.asarray(hp.dropout_rates))
    for x, y in zip(jax.tree_util.tree_leaves(scan.global_params),
                    jax.tree_util.tree_leaves(pop.global_params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-7)


# --- churn: cohorts smaller than the population ------------------------------

def test_churn_run_updates_sticky_state():
    """A 100-client population served 8 at a time under Bernoulli
    availability reaches far more than one cohort's worth of clients,
    and the store's economy arrays update only for sampled clients."""
    P, K, R = 100, 8, 5
    pop = Population(_tel(P), availability="bernoulli", sampler="uniform",
                     seed=3)
    res = run_sim("feddd", _params(jax.random.PRNGKey(0)), _tel(P),
                  _ltf, None, population=pop, cohort_size=K,
                  rounds=R, a_server=0.6, h=3, seed=0,
                  sim=SimConfig(policy="sync"))
    assert len(res.history) == R
    served = int(pop.seen.sum())
    assert K < served <= K * R
    # economy: only served clients accrue state
    assert int(pop.rounds_participated.sum()) > 0
    assert not pop.rounds_participated[~pop.seen].any()
    assert not pop.uploaded_bytes[~pop.seen].any()
    assert (pop.last_round[~pop.seen] == -1).all()
    assert pop.uploaded_bytes[pop.rounds_participated > 0].min() > 0
    # served clients' learning state was folded back (loss left the
    # all-ones prior; dropout/params parked for their next cohort)
    assert not np.array_equal(pop.loss[pop.seen], np.ones(served))
    assert len(pop._params) == served


def test_oort_cohorts_follow_utility():
    """The oort sampler's exploit slots pick the highest sticky-utility
    seen clients; exploration slots reach never-seen clients."""
    P, K = 40, 10
    pop = Population(_tel(P), sampler=make_sampler("oort", explore=0.2),
                     seed=1)
    first = pop.sample_cohort(0, K)
    assert len(first) == K and pop.first_contact(first) == K
    # mark a cohort served with huge utility for a known subset
    pop.record_round(0, first,
                     arrived=np.ones(K, bool), failed=np.zeros(K, bool),
                     losses=np.full(K, 0.5), uplink_bytes=np.full(K, 1.0),
                     utilities=np.full(K, 1e6))
    nxt = pop.sample_cohort(1, K)
    assert len(nxt) == K
    # 8 exploit slots re-pick the utility leaders, 2 explore slots are
    # reserved for never-seen clients
    assert len(np.intersect1d(nxt, first)) == 8
    assert pop.first_contact(nxt) == 2


# --- determinism: keyed draws ------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(data=st.data())
def test_uniform_draws_depend_only_on_own_client(data):
    """Each client's draw is a pure function of (seed, tag, epoch,
    client): restricting to a prefix, permuting, or subsetting the
    client axis never changes any individual draw."""
    seed = data.draw(st.integers(min_value=0, max_value=2**32 - 1))
    epoch = data.draw(st.integers(min_value=0, max_value=10_000))
    n = data.draw(st.integers(min_value=2, max_value=64))
    full = uniform_draws(seed, _TAG_AVAIL, epoch, np.arange(n))
    assert ((full >= 0.0) & (full < 1.0)).all()
    cut = data.draw(st.integers(min_value=1, max_value=n))
    np.testing.assert_array_equal(
        uniform_draws(seed, _TAG_AVAIL, epoch, np.arange(cut)),
        full[:cut])
    perm = np.asarray(data.draw(st.permutations(list(range(n)))))
    np.testing.assert_array_equal(
        uniform_draws(seed, _TAG_AVAIL, epoch, perm), full[perm])
    # availability masks restrict the same way
    model = BernoulliAvailability(n, p=0.5, seed=seed)
    sub = np.asarray(sorted(data.draw(
        st.sets(st.integers(min_value=0, max_value=n - 1), min_size=1))))
    np.testing.assert_array_equal(model.online(epoch, clients=sub),
                                  model.online(epoch)[sub])


def test_availability_models_behave():
    n = 50
    assert AlwaysOn(n).online(3).all()
    assert not BernoulliAvailability(n, p=0.0).online(0).any()
    assert BernoulliAvailability(n, p=1.0).online(0).all()
    # diurnal: per-client phases stagger on/off; duty bounds the online
    # fraction over a full period
    d = DiurnalAvailability(n, period=8.0, duty=0.5, seed=2)
    frac = np.mean([d.online(e).mean() for e in range(8)])
    assert 0.3 < frac < 0.7
    # subset consistency for the deterministic models too
    sub = np.array([0, 7, 31])
    np.testing.assert_array_equal(d.online(5, clients=sub),
                                  d.online(5)[sub])
    tr = TraceAvailability(np.eye(3, dtype=bool))
    np.testing.assert_array_equal(tr.online(4), np.eye(3, dtype=bool)[1])
    with pytest.raises(ValueError, match="unknown availability"):
        make_availability("nope", 4)
    with pytest.raises(ValueError, match="covers"):
        make_availability(AlwaysOn(3), 4)


_POP_DIGEST_SNIPPET = r"""
import hashlib
import numpy as np
from repro.core.allocation import ClientTelemetry
from repro.population import Population, make_availability, uniform_draws
from repro.population.availability import _TAG_AVAIL

h = hashlib.sha256()
ids = np.arange(257)
for epoch in (0, 1, 5, 1000):
    h.update(uniform_draws(7, _TAG_AVAIL, epoch, ids).tobytes())
for name, kw in (("bernoulli", {"p": 0.4}), ("diurnal", {"duty": 0.3})):
    m = make_availability(name, 257, seed=11, **kw)
    for epoch in range(6):
        h.update(np.packbits(m.online(epoch)).tobytes())

def tel(n):
    rng = np.random.default_rng(5)
    return ClientTelemetry(
        model_bytes=np.full(n, 1000.0),
        uplink_rate=rng.uniform(1e3, 5e3, n),
        downlink_rate=rng.uniform(5e3, 2e4, n),
        compute_latency=rng.uniform(1.0, 5.0, n),
        num_samples=rng.integers(10, 50, n).astype(float),
        label_coverage=rng.uniform(0.5, 1.0, n),
        train_loss=np.ones(n))

for sampler in ("uniform", "weighted", "oort"):
    pop = Population(tel(97), availability="bernoulli", sampler=sampler,
                     seed=3)
    for epoch in range(5):
        cohort = pop.sample_cohort(epoch, 16)
        h.update(cohort.astype(np.int64).tobytes())
        pop.record_round(epoch, cohort,
                         arrived=np.ones(16, bool),
                         failed=np.zeros(16, bool),
                         losses=np.linspace(0.1, 1.0, 16),
                         uplink_bytes=np.full(16, 10.0),
                         utilities=np.linspace(1.0, 2.0, 16))
print(h.hexdigest())
"""


def test_population_deterministic_across_processes():
    """Availability draws and cohort sampling (with evolving sticky
    state) hash identically in two fresh interpreters — the keyed-tuple
    RNG has no hidden process-local state."""
    src = str(Path(__file__).resolve().parent.parent / "src")
    digests = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", _POP_DIGEST_SNIPPET],
            capture_output=True, text=True, timeout=300,
            env={"PYTHONPATH": src, "PATH": "/usr/bin:/bin",
                 "JAX_PLATFORMS": "cpu", "HOME": "/tmp"})
        assert out.returncode == 0, out.stderr
        digests.append(out.stdout.strip())
    assert digests[0] == digests[1]
    assert len(digests[0]) == 64


# --- samplers: exact-k, top-up, guards ---------------------------------------

def test_samplers_return_exactly_k_sorted():
    pop = Population(_tel(30), seed=0)
    online = np.arange(0, 30, 2, dtype=np.int64)       # 15 online
    for name in ("uniform", "weighted", "oort"):
        s = make_sampler(name, seed=4)
        ids = s.sample(2, 10, online, pop)
        assert len(ids) == 10
        assert (np.sort(ids) == ids).all()
        assert len(np.unique(ids)) == 10
        assert np.isin(ids, online).all()              # enough online
        # scarce online set: deterministic top-up keeps k fixed
        ids = s.sample(2, 10, online[:4], pop)
        assert len(ids) == 10 and len(np.unique(ids)) == 10
        assert np.isin(online[:4], ids).all()          # online come first


def test_sampler_top_up_prefers_recent_participants():
    pop = Population(_tel(20), seed=0)
    pop.last_round[15] = 9          # most recent participant offline
    pop.last_round[12] = 4
    s = make_sampler("uniform", seed=0)
    ids = s.sample(0, 5, np.array([2, 7], dtype=np.int64), pop)
    # both online ids, then offline by last_round desc / id asc
    np.testing.assert_array_equal(ids, np.sort(np.array([2, 7, 15, 12, 0])))


def test_identity_sampler_requires_full_population():
    pop = Population(_tel(5), sampler="identity")
    np.testing.assert_array_equal(pop.sample_cohort(0, 5), np.arange(5))
    with pytest.raises(ValueError, match="identity sampler"):
        pop.sample_cohort(0, 3)
    with pytest.raises(ValueError, match="unknown cohort sampler"):
        make_sampler("nope")


# --- store: cold start and LP integration ------------------------------------

def test_cold_start_mean_replaces_unseen_lp_rows():
    """Under cold_start='mean', never-seen cohort members enter the
    Eq. (9)-(11) solve with population-mean telemetry (and the mean of
    the seen members' observed losses); seen members keep their rows.
    The default 'prior' passes telemetry through untouched."""
    P = 12
    base = _tel(P, seed=7)
    pop = Population(base, cold_start="mean")
    ids = np.array([0, 3, 5, 9])
    pop.seen[[0, 5]] = True
    cohort_tel = base.subset(ids)
    cohort_tel = cohort_tel.__class__(**{
        **{f: getattr(cohort_tel, f) for f in (
            "model_bytes", "uplink_rate", "downlink_rate",
            "compute_latency", "num_samples", "label_coverage")},
        "train_loss": np.array([0.2, 0.8, 0.4, 0.6])})
    out = pop.lp_telemetry(cohort_tel, ids)
    unseen = np.array([1, 3])                  # positions of ids 3, 9
    seen = np.array([0, 2])
    for f in ("uplink_rate", "downlink_rate", "compute_latency",
              "num_samples", "label_coverage"):
        want = float(np.mean(np.asarray(getattr(base, f), float)))
        np.testing.assert_allclose(
            np.asarray(getattr(out, f))[unseen], want)
        np.testing.assert_array_equal(
            np.asarray(getattr(out, f))[seen],
            np.asarray(getattr(cohort_tel, f))[seen])
    np.testing.assert_allclose(np.asarray(out.train_loss)[unseen],
                               np.mean([0.2, 0.4]))
    # model_bytes is structural — never averaged
    np.testing.assert_array_equal(out.model_bytes, cohort_tel.model_bytes)
    # the default passes through by identity (the bit-exactness lever)
    assert Population(base).lp_telemetry(cohort_tel, ids) is cohort_tel


def test_record_round_economy():
    pop = Population(_tel(10))
    ids = np.array([1, 4, 7])
    assert pop.first_contact(ids) == 3
    pop.record_round(2, ids,
                     arrived=np.array([True, False, True]),
                     failed=np.array([False, True, False]),
                     losses=np.array([0.3, 0.9, 0.5]),
                     uplink_bytes=np.array([100.0, 0.0, 50.0]),
                     utilities=np.array([2.0, np.nan, 3.0]))
    assert pop.first_contact(ids) == 0
    np.testing.assert_array_equal(pop.last_round[[1, 4, 7]], [2, -1, 2])
    np.testing.assert_array_equal(pop.rounds_participated[[1, 4, 7]],
                                  [1, 0, 1])
    np.testing.assert_array_equal(pop.failures[[1, 4, 7]], [0, 1, 0])
    np.testing.assert_array_equal(pop.uploaded_bytes[[1, 4, 7]],
                                  [100.0, 0.0, 50.0])
    np.testing.assert_array_equal(pop.loss[[1, 4, 7]], [0.3, 0.9, 0.5])
    assert pop.utility[1] == 2.0 and pop.utility[7] == 3.0


# --- routing and guards ------------------------------------------------------

def test_population_mode_guards():
    n = 6
    params = _params(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="cohort_size requires"):
        run_sim("feddd", params, _tel(n), _ltf, None, cohort_size=4,
                rounds=2, a_server=0.6, h=3, seed=0)
    with pytest.raises(ValueError, match="population size"):
        run_sim("feddd", params, _tel(n), _ltf, None,
                population=Population(_tel(n + 1)),
                rounds=2, a_server=0.6, h=3, seed=0)
    with pytest.raises(ValueError, match="cohort_size"):
        run_sim("feddd", params, _tel(n), _ltf, None,
                population=Population(_tel(n)), cohort_size=n + 1,
                rounds=2, a_server=0.6, h=3, seed=0)
    with pytest.raises(ValueError, match="sync/deadline/retry"):
        run_sim("feddd", params, _tel(n), _ltf, None,
                population=Population(_tel(n)), cohort_size=2,
                sim=SimConfig(policy=AsyncPolicy()),
                rounds=2, a_server=0.6, h=3, seed=0)
    with pytest.raises(ValueError, match="RunState"):
        run_sim("feddd", params, _tel(n), _ltf, None,
                population=Population(_tel(n)), checkpoint_every=1,
                rounds=2, a_server=0.6, h=3, seed=0)
    with pytest.raises(ValueError, match="cold_start"):
        Population(_tel(n), cold_start="bogus")


def test_run_scheme_population_kwarg_routes_to_simulator():
    """run_scheme(population=...) routes through the simulator even
    without an explicit sim config, and ProtocolConfig carries the
    validated population/cohort_size fields."""
    from repro.core import run_scheme
    pop = Population(_tel(10), availability="bernoulli", seed=2)
    res = run_scheme("feddd", _params(jax.random.PRNGKey(0)), _tel(10),
                     _ltf, None, population=pop, cohort_size=4,
                     rounds=3, a_server=0.6, h=3, seed=0)
    assert len(res.history) == 3
    assert int(pop.seen.sum()) >= 4
    with pytest.raises(ValueError):
        ProtocolConfig(cohort_size=4)
    with pytest.raises(ValueError):
        ProtocolConfig(population=10, cohort_size=11)


# --- observability -----------------------------------------------------------

def test_cohort_events_and_report_section(tmp_path, capsys):
    """Population runs emit one ``cohort`` event per round (population,
    cohort ids, contributors, first contacts) and the report CLI renders
    a participation section; fleet-mode logs render no such section."""
    P, K, R = 30, 6, 4
    log = tmp_path / "pop.jsonl"
    pop = Population(_tel(P), availability="bernoulli", seed=5)
    run_sim("feddd", _params(jax.random.PRNGKey(0)), _tel(P), _ltf, None,
            population=pop, cohort_size=K, rounds=R,
            a_server=0.6, h=3, seed=0, sim=SimConfig(policy="sync"),
            obs=ObsConfig(enabled=True, jsonl_path=str(log)))
    events = read_events(str(log))
    cohorts = [e for e in events if e.get("event") == "cohort"]
    assert len(cohorts) == R
    for e in cohorts:
        assert e["population"] == P
        assert e["cohort_size"] == K
        assert len(e["cohort"]) == K
        assert set(e["participated"]) <= set(e["cohort"])
        assert 0 <= e["first_contact"] <= K
    assert cohorts[0]["first_contact"] == K      # round 1: all fresh
    rc = obs_report.main([str(log)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "Cohort participation" in out
    assert f"population: {P}" in out
    assert "rounds-participated histogram" in out
    assert "first contacts/round" in out
    # fleet-mode logs don't grow the section
    clean = tmp_path / "fleet.jsonl"
    run_sim("feddd", _params(jax.random.PRNGKey(0)), _tel(4), _ltf, None,
            sim=SimConfig(policy="sync"), rounds=2,
            a_server=0.6, h=3, seed=0,
            obs=ObsConfig(enabled=True, jsonl_path=str(clean)))
    obs_report.main([str(clean)])
    assert "Cohort participation" not in capsys.readouterr().out
