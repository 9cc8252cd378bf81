"""Quickstart: FedDD federated training on a synthetic MNIST-like task.

    PYTHONPATH=src python examples/quickstart.py [--rounds 10] [--loop]

Trains the paper's MLP across 10 heterogeneous clients with differential
parameter dropout, then compares against FedAvg: same model, ~60% of the
bytes, large simulated wall-clock win.

Homogeneous FedDD runs go through the batched round engine
(core/round_engine.py) by default — one jit-compiled device step per round.
``--loop`` forces the per-client Python loop (bit-identical results, just
slower); ``benchmarks/perf_federated.py`` measures the gap.

Heterogeneous fleets (ragged width-sliced sub-models, paper §6.4) run the
same way since the shape-grouped engine: one fused step per shape group —
see ``examples/heterogeneous_models.py`` and
``benchmarks/heterogeneous.py --perf`` for that A/B.

Going faster still — multi-round scanning: when local training is
device-fused (``batched_train_fn``) and the allocator is the jit-able one
(``allocator="jax"``), ``rounds_per_dispatch=K`` runs K whole rounds —
training, masks, aggregation, dropout-rate re-allocation, round clock —
as ONE ``lax.scan`` device dispatch.  When does it pay off?  The scan
compiles once per chunk length but removes a Python dispatch + allocator
call + device->host sync PER ROUND, so it wins whenever you run enough
rounds to amortise the compile: long simulations, sweeps re-using the
compile across configs, or small/medium models where the per-round host
overhead rivals the compute (~4.7x rounds/sec over per-round engine
dispatch at 64 clients on CPU — ``benchmarks/perf_federated.py``).  For
a handful of rounds, or when you need per-round ``eval_fn`` callbacks
(like this example) or per-client Python training, stay on per-round
dispatch.

Choosing a wire codec (``--codec`` / ``--qbits``, repro.comm): the
default ``dense`` is the analytic idealization — bytes are just
``density x model_bytes``.  A real sparse upload also ships WHICH
channels survived: pick ``index`` (delta+varint) below ~12.5% upload
density, ``bitmask`` (packed bits, ceil(C/8) per leaf) above it, or
``auto`` to take the per-leaf minimum — the crossover sits at density
~1/8 because a varint gap costs ~1 byte per kept channel while the
bitmask costs C/8 regardless.  ``--qbits 8`` additionally quantizes the
uploaded values (int8 stochastic rounding) for ~4x fewer wire bytes at
a small accuracy cost; ``RoundRecord.wire_bytes`` then reports what
actually crossed the uplink next to the raw ``uploaded_bytes``
(``benchmarks/wire_formats.py`` maps the full frontier).

Fault injection (``--fault-rate`` / ``--quorum``, repro.sim.faults): a
non-zero fault rate routes the run through the event-driven simulator
and makes clients crash mid-round (rate/2), lose uplink chunks (rate,
retransmitted with exponential backoff and charged real bytes), and
occasionally ship corrupted payloads (rate/4) that the server's
validation screen quarantines.  ``--quorum`` sets the minimum number of
surviving contributors below which the server skips the round and holds
the global model (``benchmarks/fault_tolerance.py`` maps accuracy vs
fault rate).

Sharding the client axis (``--mesh N``, repro.launch.mesh): the stacked
fleet can run over an N-device ``("clients",)`` mesh — per-shard fused
training and mask building under ``shard_map``, Eq. (4) aggregated
cross-device (dense ``psum`` by default; ``mesh_collective="sparse"``
ships only each shard's surviving channels — see
``core/sparse_collective.py``).  On a 1-device mesh the learning state
is bit-identical to the batched engine; multi-device is allclose (the
psum reorders the f32 reduction).  CPUs expose one device by default, so
to try an 8-way mesh locally split the host first::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python examples/quickstart.py --mesh 8

(virtual CPU devices share the physical cores — this demonstrates the
SPMD program, real speedups need real parallel hardware;
``benchmarks/perf_federated.py --sharded`` measures the scaling curve).
``--mesh`` composes with everything except fault injection with
corruption and deadline-partial aggregation, which are single-device
engine features (the runner raises a clear error).

Survivability (``--cells`` / ``--robust-agg`` / ``--checkpoint-dir`` /
``--resume``): ``--cells K`` groups the fleet into K correlated-failure
cells, each driven by a two-state Markov outage chain
(repro.sim.outages) — a downed cell crashes ALL its members at once and
the dropout LP re-solves on the survivors.  ``--robust-agg
trimmed[:beta]`` (or ``clip[:factor]``) swaps the Eq. (4) weighted mean
for a Byzantine-robust variant fused into the same engine step — with
corrupt clients in the fleet the mean diverges while the trimmed mean
holds (``benchmarks/fault_tolerance.py`` quantifies it).
``--checkpoint-dir DIR`` snapshots the full run state atomically every
round; after a crash (or a SIGKILL), re-running with ``--resume``
continues from the last snapshot with BIT-IDENTICAL history::

    PYTHONPATH=src python examples/quickstart.py --rounds 10 \\
        --fault-rate 0.2 --cells 3 --checkpoint-dir results/ckpt
    # ... killed mid-run ...
    PYTHONPATH=src python examples/quickstart.py --rounds 10 \\
        --fault-rate 0.2 --cells 3 --checkpoint-dir results/ckpt --resume

Population-scale serving (``--population`` / ``--cohort`` /
``--availability``, repro.population): a production FL service samples a
small cohort per round from a mostly-offline population instead of
serving every registered client.  ``--population N`` registers N clients
(sticky per-client state: telemetry EWMAs, losses, dropout rates, byte
economy, per-client params), ``--cohort K`` serves K of them per round,
and ``--availability`` picks who is online (``always``, i.i.d.
``bernoulli``, or phase-staggered ``diurnal``).  Client data stays
sharded by GLOBAL id (``id % --clients``), so a client trains on the
same shard no matter which cohort it lands in.  A population the size of
the fleet with ``always`` availability is bit-identical to the plain
run.  Serving 100,000 clients costs roughly what serving the cohort
costs — the only O(population) work per round is one vectorized
availability + sampling pass::

    PYTHONPATH=src python examples/quickstart.py --rounds 10 \\
        --clients 32 --population 100000 --cohort 256 \\
        --availability bernoulli

(32 data shards, 100k registered clients, 256 served per round;
``benchmarks/population_scale.py`` maps time-to-accuracy over cohort
size x availability and pins the throughput claim.)

Observability (``--log-jsonl`` / ``--trace``, repro.obs): pass a path to
write a structured JSONL run log — one schema-versioned event per round,
pipeline span, and fault incident, derived entirely from host data the
run already pulls (no extra device syncs; with observability off the
learning state is bit-identical).  Inspect it afterwards with the
run-inspection CLI::

    PYTHONPATH=src python examples/quickstart.py --rounds 5 \\
        --fault-rate 0.2 --log-jsonl results/quickstart_run.jsonl
    PYTHONPATH=src python -m repro.obs.report results/quickstart_run.jsonl

which prints per-phase time breakdowns, the byte/failure economy, and
per-client straggler timelines, and exports CSV (``--csv``) or
Prometheus text (``--prom``).  ``--trace`` additionally wraps the host
spans in ``jax.profiler`` trace annotations so they line up with device
activity under a profiler.
"""

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402

from repro.comm import CommConfig  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import run_scheme  # noqa: E402
from repro.data import (label_coverage_score, make_dataset,  # noqa: E402
                        partition_noniid_b)
from repro.fl import (MLP_SPEC, init_cnn_spec, make_eval_fn,  # noqa: E402
                      make_local_train_fn, model_bytes,
                      sample_system_telemetry)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--clients", type=int, default=10)
    ap.add_argument("--a-server", type=float, default=0.6)
    ap.add_argument("--loop", action="store_true",
                    help="force the per-client loop instead of the "
                         "batched round engine")
    ap.add_argument("--codec", default="dense",
                    choices=("dense", "bitmask", "index", "auto"),
                    help="upload mask wire codec (repro.comm); dense is "
                         "the analytic idealization")
    ap.add_argument("--qbits", type=int, default=32, choices=(32, 16, 8),
                    help="uploaded-value precision (8 = int8 stochastic "
                         "rounding)")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="inject faults at this rate (crashes at rate/2, "
                         "lossy uplink chunks at rate, corrupted payloads "
                         "at rate/4); 0 keeps the closed-form driver")
    ap.add_argument("--quorum", type=int, default=1,
                    help="minimum surviving contributors per round; below "
                         "it the server skips the round (fault runs only)")
    ap.add_argument("--cells", type=int, default=0, metavar="K",
                    help="group clients into K correlated-failure cells, "
                         "each driven by a two-state Markov outage chain "
                         "(repro.sim.outages); composes with --fault-rate "
                         "and routes through the simulator like it")
    ap.add_argument("--robust-agg", default="mean", metavar="SPEC",
                    help="Eq. (4) aggregation variant: 'mean' (default), "
                         "'trimmed[:beta]' (coordinate-wise trimmed mean) "
                         "or 'clip[:factor]' (per-client norm clipping)")
    ap.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                    help="snapshot the full run state to DIR/run_state.npz "
                         "every round (atomic writes; survives SIGKILL)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the --checkpoint-dir snapshot; the "
                         "continued run is bit-identical to an "
                         "uninterrupted one")
    ap.add_argument("--mesh", type=int, default=None, metavar="N",
                    help="shard the client axis over an N-device mesh "
                         "(run under XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N to split a CPU host); omit for "
                         "the single-device engines")
    ap.add_argument("--population", type=int, default=None, metavar="N",
                    help="serve an N-client population (repro.population) "
                         "instead of a fixed fleet; data is sharded by "
                         "global id (id %% --clients)")
    ap.add_argument("--cohort", type=int, default=None, metavar="K",
                    help="clients served per round in population mode "
                         "(default: the whole population)")
    ap.add_argument("--availability", default="always",
                    choices=("always", "bernoulli", "diurnal"),
                    help="who is online each round in population mode")
    ap.add_argument("--log-jsonl", default=None, metavar="PATH",
                    help="write a structured JSONL run log here "
                         "(repro.obs); inspect with "
                         "`python -m repro.obs.report PATH`")
    ap.add_argument("--trace", action="store_true",
                    help="wrap host spans in jax.profiler trace "
                         "annotations (implies observability on)")
    args = ap.parse_args()
    enable_compile_cache()

    train, test = make_dataset("mnist", num_train=6000, num_test=1500)
    parts = partition_noniid_b(train, args.clients, seed=0)
    params = init_cnn_spec(jax.random.PRNGKey(0), MLP_SPEC)
    tel = sample_system_telemetry(
        args.clients, [model_bytes(params)] * args.clients,
        [len(p) for p in parts],
        [label_coverage_score(train, p) for p in parts], seed=0)
    ltf = make_local_train_fn(MLP_SPEC, train, parts, flatten=True, lr=0.1)
    ef = make_eval_fn(MLP_SPEC, test, flatten=True)

    pop_kw = {}
    fleet_n = args.clients
    if args.cohort is not None and args.population is None:
        ap.error("--cohort requires --population")
    if args.population is not None:
        from repro.population import Population
        P, shards = args.population, args.clients
        # population-sized telemetry: client g shares data shard g % C's
        # sample count / coverage, so telemetry matches the data mapping
        tel = sample_system_telemetry(
            P, [model_bytes(params)] * P,
            [len(parts[g % shards]) for g in range(P)],
            [label_coverage_score(train, parts[g % shards])
             for g in range(P)], seed=0)
        shard_ltf = ltf

        def ltf(p, gid, key):                    # noqa: F811
            return shard_ltf(p, int(gid) % shards, key)

        def make_pop():
            # one store per run: sticky state is mutated by serving
            return Population(tel, availability=args.availability,
                              sampler="uniform", seed=0)

        pop_kw["population"] = make_pop()
        pop_kw["cohort_size"] = args.cohort
        fleet_n = args.cohort if args.cohort is not None else P

    engine = "per-client loop" if args.loop else "batched round engine"
    mesh_kw = {}
    if args.mesh is not None:
        if args.loop:
            ap.error("--mesh requires the batched engine (drop --loop)")
        engine = f"sharded round engine ({args.mesh}-device mesh)"
        mesh_kw["mesh"] = args.mesh
    comm = CommConfig(codec=args.codec, qbits=args.qbits)
    obs_kw = {}
    if args.log_jsonl or args.trace:
        from repro.obs import ObsConfig
        if args.log_jsonl:
            Path(args.log_jsonl).parent.mkdir(parents=True, exist_ok=True)
        obs_kw["obs"] = ObsConfig(enabled=True, jsonl_path=args.log_jsonl,
                                  trace=args.trace)
    faults = None
    if args.fault_rate > 0.0:
        from repro.sim import FaultConfig, RandomFaults
        faults = RandomFaults(FaultConfig(
            crash_rate=args.fault_rate / 2, loss_rate=args.fault_rate,
            corrupt_rate=args.fault_rate / 4, quorum=args.quorum, seed=0))
    if args.cells > 0:
        from repro.sim import CellOutageModel, OutageConfig
        faults = CellOutageModel(
            args.clients,
            OutageConfig(cells=args.cells, p_out=0.15, p_back=0.5, seed=0),
            inner=faults)
    surv_kw = {}
    if args.robust_agg != "mean":
        surv_kw["robust_agg"] = args.robust_agg
    if args.checkpoint_dir:
        ckpt = str(Path(args.checkpoint_dir) / "run_state.npz")
        surv_kw["checkpoint_every"] = 1
        surv_kw["checkpoint_path"] = ckpt
        if args.resume:
            if not Path(ckpt).exists():
                ap.error(f"--resume: no checkpoint at {ckpt}")
            surv_kw["resume_from"] = ckpt
    elif args.resume:
        ap.error("--resume requires --checkpoint-dir")
    pop_col = (f", population={args.population}/cohort={fleet_n}"
               f"/{args.availability}" if args.population else "")
    if faults is not None:
        cells_col = f", cells={args.cells}" if args.cells else ""
        print(f"== FedDD + faults (rate={args.fault_rate}, "
              f"quorum={args.quorum}{cells_col}, "
              f"agg={args.robust_agg}{pop_col}) ==")
    else:
        print(f"== FedDD (A_server={args.a_server}, {engine}, "
              f"codec={args.codec}/q{args.qbits}, "
              f"agg={args.robust_agg}{pop_col}) ==")
    feddd = run_scheme("feddd", params, tel, ltf, ef, rounds=args.rounds,
                       a_server=args.a_server, h=5, batched=not args.loop,
                       comm=comm, faults=faults, **mesh_kw, **obs_kw,
                       **surv_kw, **pop_kw)
    if args.log_jsonl:
        print(f"  run log -> {args.log_jsonl}  (inspect: python -m "
              f"repro.obs.report {args.log_jsonl})")
    for r in feddd.history:
        fault_col = ""
        if faults is not None:
            fault_col = (" SKIPPED" if r.skipped else
                         f"  surv={r.survivors}/{fleet_n}")
        print(f"  round {r.round:2d}  acc={r.metrics['accuracy']:.3f}  "
              f"sim_t={r.sim_time:8.1f}s  uploaded={r.uploaded_fraction:.0%}  "
              f"wire={r.wire_bytes / 1e3:.0f}kB  "
              f"host={r.host_wall_time:.2f}s{fault_col}")

    print("== FedAvg (full uploads) ==")
    if args.population is not None:
        pop_kw["population"] = make_pop()     # fresh sticky state
    fedavg = run_scheme("fedavg", params, tel, ltf, ef, rounds=args.rounds,
                        **pop_kw)
    for r in fedavg.history[-3:]:
        print(f"  round {r.round:2d}  acc={r.metrics['accuracy']:.3f}  "
              f"sim_t={r.sim_time:8.1f}s")

    tgt = 0.9
    t_dd, t_avg = (x.time_to_accuracy(tgt) for x in (feddd, fedavg))
    if t_dd and t_avg:
        print(f"\nTime to {tgt:.0%} accuracy: FedDD {t_dd:.0f}s vs "
              f"FedAvg {t_avg:.0f}s  ({1 - t_dd / t_avg:.0%} reduction)")


if __name__ == "__main__":
    main()
