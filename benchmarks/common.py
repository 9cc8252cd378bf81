"""Shared FL experiment harness for the paper-figure benchmarks.

Each benchmark module reproduces one paper table/figure on the synthetic
datasets (DESIGN.md §8).  ``run_experiment`` wires dataset + partition +
scheme and returns the round history; ``run_sim_experiment`` routes the
same setup through the event-driven simulator (repro/sim) with a chosen
aggregation policy and network model; ``csv_row`` prints the harness's
``name,us_per_call,derived`` convention (derived = the figure's headline
quantity).

Two time axes appear in results — never mix them:

* ``RoundRecord.sim_time`` / ``sim_round_time`` — SIMULATED seconds on the
  paper's Eq. (12) clock (what the modelled clients would take).  All
  time-to-accuracy figures are on this axis.
* ``RoundRecord.host_wall_time`` (and the ``us_per_call`` column emitted
  by :func:`csv_row` via :func:`timed`) — REAL host seconds this
  implementation spent computing; a throughput measure only.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.obs import MetricsRegistry  # noqa: E402

from repro.core import run_scheme  # noqa: E402
from repro.core.selection import SelectionConfig  # noqa: E402
from repro.data import (label_coverage_score, make_dataset,  # noqa: E402
                        partition_class_imbalanced, partition_iid,
                        partition_noniid_a, partition_noniid_b)
from repro.fl import (CNN1_SPEC, CNN2_SPEC, MLP_SPEC,  # noqa: E402
                      HETERO_A_SPECS, HETERO_B_SPECS, init_cnn_spec,
                      make_eval_fn, make_local_train_fn, model_bytes,
                      sample_system_telemetry)

PARTITIONS = {
    "iid": partition_iid,
    "noniid_a": partition_noniid_a,
    "noniid_b": partition_noniid_b,
    "imbalanced": partition_class_imbalanced,
}

DATASET_MODEL = {
    "mnist": (MLP_SPEC, True, 0.1),      # (spec, flatten, lr)
    "fmnist": (CNN1_SPEC, False, 0.05),
    "cifar10": (CNN2_SPEC, False, 0.05),
}


def setup_experiment(
    dataset: str = "mnist",
    partition: str = "noniid_b",
    *,
    num_clients: int = 10,
    num_train: int = 4000,
    num_test: int = 1000,
    hetero_specs: Optional[List] = None,
    per_class_eval: bool = False,
    seed: int = 0,
):
    """Dataset + partition + model + telemetry plumbing shared by the
    protocol-driver and sim-driver entry points.

    Returns ``(global_params, telemetry, local_train_fn, eval_fn,
    client_params)`` (client_params is None for homogeneous runs).
    Turns on the persistent compile cache (repro.compile_cache) first.
    """
    enable_compile_cache()
    train, test = make_dataset(dataset, num_train=num_train,
                               num_test=num_test, seed=seed)
    parts = PARTITIONS[partition](train, num_clients, seed=seed)
    if hetero_specs is not None:
        specs = [hetero_specs[i % len(hetero_specs)]
                 for i in range(num_clients)]
        clients = [init_cnn_spec(jax.random.PRNGKey(100 + i), s)
                   for i, s in enumerate(specs)]
        global_params = init_cnn_spec(jax.random.PRNGKey(0), hetero_specs[0])
        lr = 0.05
        fns = [make_local_train_fn(specs[i], train, parts, lr=lr)
               for i in range(num_clients)]

        def ltf(params, idx, rng):
            return fns[idx](params, idx, rng)

        ef = make_eval_fn(hetero_specs[0], test, per_class=per_class_eval)
        mbytes = [model_bytes(p) for p in clients]
    else:
        spec, flatten, lr = DATASET_MODEL[dataset]
        clients = None
        global_params = init_cnn_spec(jax.random.PRNGKey(0), spec)
        ltf = make_local_train_fn(spec, train, parts, flatten=flatten, lr=lr)
        ef = make_eval_fn(spec, test, flatten=flatten,
                          per_class=per_class_eval)
        mbytes = [model_bytes(global_params)] * num_clients
    tel = sample_system_telemetry(
        num_clients, mbytes, [len(p) for p in parts],
        [label_coverage_score(train, p) for p in parts], seed=seed)
    return global_params, tel, ltf, ef, clients


def run_experiment(
    dataset: str = "mnist",
    partition: str = "noniid_b",
    scheme: str = "feddd",
    *,
    num_clients: int = 10,
    rounds: int = 10,
    num_train: int = 4000,
    num_test: int = 1000,
    a_server: float = 0.6,
    d_max: float = 0.8,
    delta: float = 1.0,
    h: int = 5,
    selection_scheme: str = "feddd",
    hetero_specs: Optional[List] = None,
    per_class_eval: bool = False,
    seed: int = 0,
    batched: bool = True,
    comm=None,
):
    global_params, tel, ltf, ef, clients = setup_experiment(
        dataset, partition, num_clients=num_clients, num_train=num_train,
        num_test=num_test, hetero_specs=hetero_specs,
        per_class_eval=per_class_eval, seed=seed)
    extra = {} if comm is None else {"comm": comm}
    return run_scheme(scheme, global_params, tel, ltf, ef,
                      client_params=clients, rounds=rounds,
                      a_server=a_server, d_max=d_max, delta=delta, h=h,
                      selection=SelectionConfig(scheme=selection_scheme),
                      seed=seed, batched=batched, **extra)


def run_sim_experiment(
    dataset: str = "mnist",
    partition: str = "noniid_b",
    scheme: str = "feddd",
    *,
    policy: str = "sync",
    network: str = "static",
    num_clients: int = 10,
    rounds: int = 10,
    num_train: int = 4000,
    num_test: int = 1000,
    a_server: float = 0.6,
    d_max: float = 0.8,
    delta: float = 1.0,
    h: int = 5,
    seed: int = 0,
    network_kw: Optional[Dict] = None,
    policy_kw: Optional[Dict] = None,
    eval_every: int = 1,
    hetero_specs: Optional[List] = None,
    faults=None,
    robust_agg: str = "mean",
):
    """The same experiment, time axis owned by the event-driven simulator
    (repro/sim): ``policy`` in {sync, deadline, retry, async}, ``network``
    in {static, markov, straggler} (see repro.sim.network for trace-driven
    models).  ``hetero_specs`` builds a ragged-width fleet — the sim
    drives the shape-grouped engine, so stragglers x ragged models
    compose.  ``faults`` attaches a :class:`repro.sim.faults.FaultModel`
    (churn / lossy uplinks / corruption / quorum degradation)."""
    from repro.sim import SimConfig, make_network, run_sim

    global_params, tel, ltf, ef, clients = setup_experiment(
        dataset, partition, num_clients=num_clients, num_train=num_train,
        num_test=num_test, hetero_specs=hetero_specs, seed=seed)
    net = make_network(network, tel, seed=seed, **(network_kw or {}))
    sim = SimConfig(policy=policy, policy_kw=policy_kw or {},
                    eval_every=eval_every)
    return run_sim(scheme, global_params, tel, ltf, ef, sim=sim,
                   network=net, client_params=clients, rounds=rounds,
                   a_server=a_server, d_max=d_max, delta=delta, h=h,
                   seed=seed, faults=faults, robust_agg=robust_agg)


# One registry per benchmark process: every csv_row feeds it, and
# ``benchmarks/run.py`` exports the whole sweep as Prometheus text
# (results/benchmarks.prom) after the module loop.
REGISTRY = MetricsRegistry()


def csv_row(name: str, wall_s: float, derived: str) -> str:
    """``us_per_call`` is HOST time (from :func:`timed`) — simulated-clock
    quantities belong in the ``derived`` column."""
    REGISTRY.set("benchmark_us_per_call", wall_s * 1e6, name=name)
    return f"{name},{wall_s * 1e6:.0f},{derived}"


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


# -- artifact writers (shared by all benchmarks/*.py modules) -------------

def write_json(out_dir: Path, filename: str, payload) -> Path:
    """Write a JSON artifact under ``out_dir`` (mkdir'd), newline-terminated."""
    out_dir = Path(out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / filename
    path.write_text(json.dumps(payload, indent=1) + "\n")
    return path


def write_table(out_dir: Path, filename: str, lines: List[str]) -> Path:
    """Write a line-oriented artifact (CSV/markdown table) under ``out_dir``."""
    out_dir = Path(out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / filename
    path.write_text("\n".join(lines) + "\n")
    return path


def export_registry(out_dir: Path, filename: str = "benchmarks.prom") -> Path:
    """Dump the process-wide :data:`REGISTRY` as Prometheus text."""
    out_dir = Path(out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / filename
    path.write_text(REGISTRY.prometheus_text())
    return path
