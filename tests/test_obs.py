"""Observability layer (repro/obs): the disabled-obs bit-identity
contract, the enabled-obs no-extra-transfer contract, and the pieces —
metrics registry, JSONL run log round-trips, fault incident events, and
the run-inspection CLI.

Pins the observability axis's contracts (mirroring the zero-rate-faults
contract of tests/test_faults.py):

* inert default — ``ObsConfig()`` resolves to the shared NULL_RECORDER
  and leaves learning state BIT-IDENTICAL on all four execution paths
  (reference loop / batched engine / grouped engine / scanned engine)
  and the event-driven simulator;
* no new syncs — enabling JSONL logging performs the same number of
  ``jax.device_get`` calls as a disabled run, and triggers no engine
  recompilation (the ``jax.named_scope`` annotations are unconditional
  compile-time metadata);
* run-log fidelity — the JSONL log round-trips to the identical
  RoundRecord history (float64 repr exactness), fault incidents appear
  as one event each, and byte counters equal the history sums;
* RoundRecord invariants — wire/uploaded consistency and zeroed
  failure-economy fields on every fault-free path;
* the report CLI renders phase/byte/failure/straggler sections from a
  real log and exports CSV + Prometheus text.
"""

import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import FedDDServer, ProtocolConfig, run_scheme
from repro.core.allocation import ClientTelemetry
from repro.obs import (NULL_RECORDER, MetricsRegistry, ObsConfig,
                       make_recorder, load_history, read_events)
from repro.obs import report as obs_report
from repro.sim import FaultConfig, RandomFaults, ScriptedFaults, SimConfig, \
    run_sim

pytestmark = pytest.mark.flcore


# --- shared fixtures ---------------------------------------------------------

def _params(key, w=12):
    k1, k2 = jax.random.split(key)
    return {"fc0": {"w": jax.random.normal(k1, (20, w)), "b": jnp.zeros(w)},
            "fc1": {"w": jax.random.normal(k2, (w, 5)), "b": jnp.zeros(5)}}


def _nbytes(p):
    return float(sum(l.size * l.dtype.itemsize
                     for l in jax.tree_util.tree_leaves(p)))


def _tel(n, nbytes, seed=0):
    rng = np.random.default_rng(seed)
    return ClientTelemetry(
        model_bytes=np.full(n, nbytes) if np.isscalar(nbytes)
        else np.asarray(nbytes),
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


def _histories_equal(ha, hb):
    assert len(ha) == len(hb)
    for ra, rb in zip(ha, hb):
        assert ra.round == rb.round
        assert ra.mean_loss == rb.mean_loss
        assert ra.sim_time == rb.sim_time
        assert ra.uploaded_bytes == rb.uploaded_bytes
        assert ra.wire_bytes == rb.wire_bytes
        assert ra.participants == rb.participants
        np.testing.assert_array_equal(ra.dropout_rates, rb.dropout_rates)


def _ragged_fleet(n=6, seed=0):
    widths = (12, 8, 6)
    gp = _params(jax.random.PRNGKey(seed), max(widths))
    clients = [_params(jax.random.PRNGKey(seed + 100 + i),
                       widths[i % len(widths)]) for i in range(n)]
    return gp, clients


def _scan_fixture(n=8, seed=0):
    params = _params(jax.random.PRNGKey(seed))
    tel = _tel(n, _nbytes(params), seed=seed)

    @jax.jit
    def batched(stacked, key):
        new = jax.tree_util.tree_map(
            lambda x: x * 0.99 + 0.01 * jax.random.normal(
                jax.random.fold_in(key, 1), x.shape), stacked)
        l0 = jax.tree_util.tree_leaves(new)[0]
        losses = jnp.mean(jnp.abs(l0.reshape(l0.shape[0], -1)), axis=1)
        return new, losses

    return params, tel, batched


# --- metrics registry --------------------------------------------------------

def test_registry_counter_gauge_labels():
    reg = MetricsRegistry()
    reg.inc("req_total", 1, path="a")
    reg.inc("req_total", 2, path="a")
    reg.inc("req_total", 5, path="b")
    reg.set("temp", 3.5, room="x")
    reg.set("temp", 4.5, room="x")          # gauges overwrite
    assert reg.value("req_total", path="a") == 3.0
    assert reg.value("req_total", path="b") == 5.0
    assert reg.value("temp", room="x") == 4.5
    with pytest.raises(ValueError):
        reg.inc("req_total", -1, path="a")  # counters only go up
    with pytest.raises(ValueError):
        reg.set("req_total", 1.0)           # kind conflict


def test_registry_histogram_prometheus_cumulative():
    reg = MetricsRegistry()
    for v in (0.002, 0.002, 0.7, 100.0):
        reg.observe("lat_seconds", v)
    text = reg.prometheus_text()
    lines = {l.split(" ")[0]: float(l.split(" ")[1])
             for l in text.splitlines() if l.startswith("lat_seconds")}
    assert lines['lat_seconds_bucket{le="+Inf"}'] == 4.0
    assert lines['lat_seconds_count'] == 4.0
    assert lines['lat_seconds_sum'] == pytest.approx(100.704)
    assert lines['lat_seconds_bucket{le="0.005"}'] == 2.0
    assert lines['lat_seconds_bucket{le="1"}'] == 3.0
    # cumulative counts are monotone non-decreasing in file (= le) order
    les = [float(l.split(" ")[1]) for l in text.splitlines()
           if "_bucket" in l]
    assert all(a <= b for a, b in zip(les, les[1:]))


def test_registry_csv_rows():
    reg = MetricsRegistry()
    reg.inc("n_total", 2, k="v")
    rows = reg.csv_rows()
    assert rows[0] == "metric,labels,value"
    assert any("n_total" in r and "k=v" in r for r in rows[1:])


# --- inert default -----------------------------------------------------------

def test_default_obsconfig_is_inert():
    assert ObsConfig().active is False
    assert make_recorder(ObsConfig(), driver="x") is NULL_RECORDER
    assert make_recorder(None, driver="x") is NULL_RECORDER
    # any field set activates
    assert ObsConfig(enabled=True).active
    assert ObsConfig(jsonl_path="/tmp/x").active
    assert ObsConfig(trace=True).active
    assert ObsConfig(registry=MetricsRegistry()).active
    # the null recorder's hooks are callable no-ops
    with NULL_RECORDER.span("phase"):
        pass
    NULL_RECORDER.event("x", kind="collides_fine")
    NULL_RECORDER.close()


# --- bit-identity: obs-on == obs-off on every path ---------------------------

def _run_path(path, obs, tmp_path):
    n = 6
    kw = dict(rounds=4, a_server=0.6, h=3, seed=0)
    if obs:
        kw["obs"] = ObsConfig(enabled=True,
                              jsonl_path=str(tmp_path / f"{path}.jsonl"))
    if path == "loop":
        params = _params(jax.random.PRNGKey(0))
        return run_scheme("feddd", params, _tel(n, _nbytes(params)), _ltf,
                          None, batched=False, **kw)
    if path == "engine":
        params = _params(jax.random.PRNGKey(0))
        return run_scheme("feddd", params, _tel(n, _nbytes(params)), _ltf,
                          None, batched=True, **kw)
    if path == "grouped":
        gp, clients = _ragged_fleet(n)
        tel = _tel(n, [_nbytes(p) for p in clients])
        return run_scheme("feddd", gp, tel, _ltf, None,
                          client_params=clients, **kw)
    if path == "scanned":
        params, tel, batched = _scan_fixture()
        cfg = ProtocolConfig(scheme="feddd", allocator="jax",
                             rounds_per_dispatch=2, **kw)
        return FedDDServer(params, cfg, tel).run(batched_train_fn=batched)
    if path == "sim":
        params = _params(jax.random.PRNGKey(0))
        return run_sim("feddd", params, _tel(n, _nbytes(params)), _ltf,
                       None, sim=SimConfig(policy="sync"),
                       faults=RandomFaults(FaultConfig(
                           crash_rate=0.2, loss_rate=0.3, seed=0)), **kw)
    raise AssertionError(path)


@pytest.mark.parametrize("path", ["loop", "engine", "grouped", "scanned",
                                  "sim"])
def test_obs_enabled_is_bit_identical(path, tmp_path):
    """THE acceptance contract: enabling observability (with a JSONL log)
    changes no learning state on any execution path."""
    ref = _run_path(path, False, tmp_path)
    got = _run_path(path, True, tmp_path)
    assert _trees_equal(ref.global_params, got.global_params)
    _histories_equal(ref.history, got.history)


def test_obs_disabled_leaves_null_recorder(tmp_path):
    params = _params(jax.random.PRNGKey(0))
    srv = FedDDServer(params, ProtocolConfig(scheme="feddd", rounds=2),
                      _tel(4, _nbytes(params)))
    srv.run(_ltf)
    assert srv.obs is NULL_RECORDER


# --- no new device->host transfers, no recompiles ----------------------------

def test_obs_enabled_adds_no_device_transfers(tmp_path, monkeypatch):
    """Recording consumes only host data the run already pulls: the
    number of ``jax.device_get`` calls is identical obs-on vs obs-off."""
    counts = {"n": 0}
    real = jax.device_get

    def counting(x):
        counts["n"] += 1
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)
    _run_path("engine", False, tmp_path)
    off = counts["n"]
    counts["n"] = 0
    _run_path("engine", True, tmp_path)
    assert counts["n"] == off


def test_obs_enabled_triggers_no_recompile(tmp_path):
    """The named_scope phase annotations are unconditional compile-time
    metadata: an obs-on run reuses the obs-off engine compile."""
    from repro.core.round_engine import _round_step

    _run_path("engine", False, tmp_path)          # warm the jit cache
    warm = _round_step._cache_size()
    _run_path("engine", True, tmp_path)
    assert _round_step._cache_size() == warm


# --- span vocabulary: the driver's and executors' host moments ---------------

@pytest.mark.parametrize("path", ["engine", "grouped", "scanned"])
def test_run_spans_cover_stack_records_and_unstack(path, tmp_path):
    """Every host moment of ``FedDDServer.run`` that can hold the device
    idle is a span: the executor's build and finalize once per run, the
    round bookkeeping once per round (per chunk when scanned), and the
    per-client loop's unstack / client / restack inside local_train."""
    from repro.obs import PHASES
    _run_path(path, True, tmp_path)
    spans = [e for e in read_events(str(tmp_path / f"{path}.jsonl"))
             if e["event"] == "span"]
    names = [e["name"] for e in spans]
    rounds, clients = 4, (8 if path == "scanned" else 6)
    assert names.count("fleet_stack") == 1
    assert names.count("fleet_unstack") == 1
    assert names[0] == "fleet_stack" and names[-1] == "fleet_unstack"
    assert names.count("round_records") == (2 if path == "scanned"
                                            else rounds)
    groups = {"engine": 1, "grouped": 3, "scanned": 0}[path]
    per_client = groups > 0
    assert names.count("client_train") == rounds * clients * per_client
    assert names.count("group_unstack") == rounds * groups
    assert names.count("group_stack") == rounds * groups
    parents = {e["name"]: e["parent"] for e in spans}
    for nested in ("client_train", "group_unstack", "group_stack"):
        if per_client:
            assert parents[nested] == "local_train"
    for top in ("fleet_stack", "fleet_unstack", "round_records"):
        assert parents[top] is None
    assert set(names) <= set(PHASES)


def test_fleet_spans_close_with_a_failing_executor(tmp_path):
    """The executor is built inside the recorder's lifetime: a
    configuration error there still closes the run log."""
    gp, clients = _ragged_fleet(6)
    log = tmp_path / "bad.jsonl"
    cfg = ProtocolConfig(scheme="feddd", rounds=1, mesh=1,
                         mesh_collective="sparse",
                         obs=ObsConfig(jsonl_path=str(log)))
    srv = FedDDServer(gp, cfg, _tel(6, [_nbytes(p) for p in clients]),
                      client_params=clients)
    with pytest.raises(ValueError):
        srv.run(_ltf)
    assert srv.obs is NULL_RECORDER
    assert read_events(str(log))[-1]["event"] == "run_end"


def test_compile_counter_counts_then_reads_zero_warm(tmp_path):
    """The recorder counts the programs JAX compiles while it is open: a
    fleet size no other test uses compiles, the same run again does not
    (and the run log carries one ``compile`` event per count)."""
    params = _params(jax.random.PRNGKey(0))
    tel = _tel(7, _nbytes(params))
    counts = []
    for k in range(2):
        reg = MetricsRegistry()
        log = tmp_path / f"compile{k}.jsonl"
        run_scheme("feddd", params, tel, _ltf, None, rounds=2, a_server=0.6,
                   h=3, seed=0, obs=ObsConfig(registry=reg,
                                              jsonl_path=str(log)))
        n = reg.value("feddd_compiles_total", kind="compile") or 0.0
        events = [e for e in read_events(str(log))
                  if e["event"] == "compile" and e["kind"] == "compile"]
        assert len(events) == n
        assert all(e["seconds"] >= 0.0 for e in events)
        counts.append(n)
    assert counts[0] >= 1
    assert counts[1] == 0


# --- JSONL run log -----------------------------------------------------------

def test_jsonl_roundtrips_history_exactly(tmp_path):
    res = _run_path("engine", True, tmp_path)
    hist = load_history(str(tmp_path / "engine.jsonl"))
    assert len(hist) == len(res.history)
    for a, b in zip(res.history, hist):
        assert a.round == b.round
        assert a.mean_loss == b.mean_loss          # float64 repr exact
        assert a.sim_time == b.sim_time
        assert a.uploaded_bytes == b.uploaded_bytes
        assert a.wire_bytes == b.wire_bytes
        assert a.host_wall_time == b.host_wall_time
        np.testing.assert_array_equal(np.asarray(a.dropout_rates),
                                      np.asarray(b.dropout_rates))


def test_jsonl_schema_and_event_stream(tmp_path):
    _run_path("engine", True, tmp_path)
    events = read_events(str(tmp_path / "engine.jsonl"))
    assert events[0]["event"] == "run_start"
    assert events[0]["driver"] == "protocol"
    assert events[-1]["event"] == "run_end"
    kinds = {e["event"] for e in events}
    assert {"span", "round"} <= kinds
    spans = {e["name"] for e in events if e["event"] == "span"}
    assert {"local_train", "engine_step", "host_transfer",
            "allocate"} <= spans
    rounds = [e for e in events if e["event"] == "round"]
    assert [e["round"] for e in rounds] == [1, 2, 3, 4]
    assert all(e["path"] == "engine" and e["scheme"] == "feddd"
               for e in rounds)


def test_registry_totals_match_history(tmp_path):
    """The account_uplink hook feeds the byte counters exactly once per
    round: registry totals == history sums."""
    reg = MetricsRegistry()
    params = _params(jax.random.PRNGKey(0))
    res = run_scheme("feddd", params, _tel(6, _nbytes(params)), _ltf, None,
                     rounds=3, a_server=0.6, h=3, seed=0,
                     obs=ObsConfig(enabled=True, registry=reg))
    assert reg.value("feddd_uploaded_bytes_total") == pytest.approx(
        sum(r.uploaded_bytes for r in res.history))
    assert reg.value("feddd_wire_bytes_total") == pytest.approx(
        sum(r.wire_bytes for r in res.history))
    assert reg.value("feddd_rounds_total", scheme="feddd",
                     path="engine") == 3.0


# --- RoundRecord invariants (fault-free, all four paths) ---------------------

@pytest.mark.parametrize("path", ["loop", "engine", "grouped", "scanned"])
def test_round_record_invariants(path, tmp_path):
    res = _run_path(path, False, tmp_path)
    for r in res.history:
        # default dense comm charges exactly the analytic bytes
        assert r.wire_bytes == r.uploaded_bytes
        assert r.uploaded_bytes > 0.0
        # failure economy is all-zero without a fault model
        assert r.survivors == r.participants
        assert r.retries == 0
        assert r.abandoned_bytes == 0.0
        assert r.quarantined_bytes == 0.0
        assert not r.skipped


# --- fault incidents ---------------------------------------------------------

def test_fault_incident_events(tmp_path):
    """A scripted crash surfaces as exactly one fault event with the
    incident's own kind; a quorum skip logs the skipped round and the
    skip incident, and the skipped record round-trips."""
    n = 4
    params = _params(jax.random.PRNGKey(0))
    tel = _tel(n, _nbytes(params))
    log = tmp_path / "faults.jsonl"
    # fault epochs are 0-indexed rounds: epoch 1 -> logged round 2
    # (client 1 crashes); epoch 2 -> round 3 (all crash -> quorum skip)
    faults = ScriptedFaults(
        crashes={(1, 1): 0.5, **{(2, i): 0.5 for i in range(n)}},
        config=FaultConfig(quorum=1))
    res = run_sim("feddd", params, tel, _ltf, None,
                  sim=SimConfig(policy="sync"), faults=faults,
                  rounds=3, a_server=0.6, h=3, seed=0,
                  obs=ObsConfig(enabled=True, jsonl_path=str(log)))
    events = read_events(str(log))
    crashes = [e for e in events
               if e["event"] == "fault" and e["kind"] == "crash"]
    assert len(crashes) == 1 + n
    assert any(e["round"] == 2 and e["client"] == 1 for e in crashes)
    skips = [e for e in events
             if e["event"] == "fault" and e["kind"] == "quorum_skip"]
    assert len(skips) == 1 and skips[0]["round"] == 3
    assert res.history[-1].skipped
    hist = load_history(str(log))
    assert hist[-1].skipped and hist[-1].survivors == 0


# --- report CLI --------------------------------------------------------------

def test_report_cli_renders_and_exports(tmp_path, capsys):
    _run_path("sim", True, tmp_path)
    log = str(tmp_path / "sim.jsonl")
    csv = tmp_path / "rounds.csv"
    prom = tmp_path / "metrics.prom"
    rc = obs_report.main([log, "--csv", str(csv), "--prom", str(prom)])
    assert rc == 0
    out = capsys.readouterr().out
    for section in ("Phase breakdown", "Byte economy", "Failure economy",
                    "Straggler timeline"):
        assert section in out, section
    assert "local_train" in out
    # CSV: header + one line per non-skipped... every round logs one row
    lines = csv.read_text().strip().splitlines()
    assert lines[0].startswith("round,")
    assert len(lines) == 1 + 4
    # Prometheus replay uses the same round->metrics mapping as live runs
    ptext = prom.read_text()
    assert "feddd_rounds_total" in ptext
    assert "feddd_sim_time_seconds" in ptext


def test_report_nests_child_spans_under_their_parent(tmp_path, capsys):
    """Child spans (client_train in local_train) are listed indented
    under their parent and left out of the shares, which sum to 100 %."""
    _run_path("grouped", True, tmp_path)
    assert obs_report.main([str(tmp_path / "grouped.jsonl")]) == 0
    out = capsys.readouterr().out
    block = out.split("Phase breakdown")[1].split("\n\n")[0]
    rows = [l for l in block.splitlines()[3:] if l.strip()]
    top = [l for l in rows if not l.startswith("    ")]
    child = [l for l in rows if l.startswith("    ")]
    assert {l.split()[0] for l in child} == {"client_train",
                                             "group_unstack", "group_stack"}
    # every child row follows its parent's row
    first_child = rows.index(child[0])
    assert rows[first_child - 1].split()[0] == "local_train"
    shares = [float(l.split()[-1].rstrip("%")) for l in top]
    assert sum(shares) == pytest.approx(100.0, abs=0.1 * len(top))
    assert "Compiles (jax.monitoring)" in out


def test_report_cli_rejects_non_runlog(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"event":"round"}\n')
    with pytest.raises(ValueError):
        obs_report.main([str(bad)])


# --- committed benchmark baseline (CI regression gate input) -----------------

def test_bench_trajectory_present():
    """results/BENCH_round_engine.json is a committed artifact the CI
    perf gate diffs against — its absence must fail loudly, not skip."""
    path = Path(__file__).resolve().parents[1] / "results" / \
        "BENCH_round_engine.json"
    assert path.exists(), (
        "results/BENCH_round_engine.json missing — regenerate with "
        "`python benchmarks/run.py --json` and commit it")
    payload = json.loads(path.read_text())
    assert "clients" in payload and payload["clients"]
    assert "acceptance" in payload
    for per in payload["clients"].values():
        assert "scanned" in per and "rounds_per_sec" in per["scanned"]


def test_report_renders_outage_windows(tmp_path, capsys):
    """Correlated cell outages appear as an 'Outage windows' section:
    closed windows with durations, open windows flagged, members listed."""
    from repro.sim import CellOutageModel, OutageConfig
    n = 4
    params = _params(jax.random.PRNGKey(0))
    tel = _tel(n, _nbytes(params))
    log = tmp_path / "outages.jsonl"
    # p_out = p_back = 1: cells alternate down/up from epoch 1, so the
    # log holds one closed window (duration 1) and one still open
    run_sim("feddd", params, tel, _ltf, None,
            sim=SimConfig(policy="sync"),
            faults=CellOutageModel(
                n, OutageConfig(cells=2, p_out=1.0, p_back=1.0)),
            rounds=4, a_server=0.6, h=3, seed=0,
            obs=ObsConfig(enabled=True, jsonl_path=str(log)))
    rc = obs_report.main([str(log)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "Outage windows" in out
    assert "cell 0" in out and "cell 1" in out
    assert "epoch down" in out            # a closed window with duration
    assert "still down at end" in out     # an open window
    assert "members 0,2" in out           # round-robin cell 0 of n=4
    # a log with no outage incidents renders no outage section
    clean = tmp_path / "clean.jsonl"
    run_sim("feddd", params, tel, _ltf, None,
            sim=SimConfig(policy="sync"),
            rounds=2, a_server=0.6, h=3, seed=0,
            obs=ObsConfig(enabled=True, jsonl_path=str(clean)))
    obs_report.main([str(clean)])
    assert "Outage windows" not in capsys.readouterr().out
