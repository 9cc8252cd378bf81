"""Loop-vs-engine A/B for the FedDD round engine (rounds/sec).

Runs the same homogeneous FedDD simulation four ways and reports
rounds/sec + the speedup over the per-client loop:

  loop     — ProtocolConfig(batched=False): the original Python loop over
             clients (per-client build_masks dispatches, per-leaf float()
             host syncs, list-based aggregation);
  batched  — ProtocolConfig(batched=True): per-client Python training, but
             the whole server side of the round is ONE jitted device step
             (core/round_engine.py);
  fused    — batched_train_fn: local training vmapped over clients too, so
             the entire round is device-resident and the only host traffic
             is the per-round (losses, densities) telemetry struct;
  scanned  — rounds_per_dispatch=K: the round LOOP fuses too — K rounds
             (training, masks, Eq. (4)-(6), the Eq. (9)-(11) re-allocation
             and the Eq. (12) clock) run as ONE lax.scan dispatch with a
             single stacked-telemetry transfer per chunk.

All modes run ``allocator="jax"`` so results are bit-comparable across the
whole axis (the scanned path requires the traceable allocator; the
sequential paths accept either — tests/test_allocation.py pins the
numpy/jax parity).  All four produce bit-identical global parameters for a
fixed seed (also asserted by tests/test_round_engine.py); the A/B prints
the max deviation.

    PYTHONPATH=src python benchmarks/perf_federated.py \
        [--clients 64] [--rounds 5] [--rounds-per-dispatch 8] [--use-kernel]

``--smoke`` is the CI parity gate: tiny grid (8 clients, 2 rounds, K=2),
no perf thresholds, non-zero exit unless the scanned digests (params +
history) exactly match sequential dispatch.  ``run()`` (the
benchmarks/run.py entry) writes ``results/perf_federated.csv``;
``bench_json()`` writes the machine-readable rounds/sec trajectory
``results/BENCH_round_engine.json`` (16/64 clients) that CI uploads so
future PRs can track engine regressions.
"""

from __future__ import annotations

import argparse
import hashlib
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import csv_row, write_json, write_table  # noqa: E402
from repro.core import FedDDServer, ProtocolConfig  # noqa: E402
from repro.core.round_engine import make_batched_train_fn  # noqa: E402
from repro.core.selection import SelectionConfig  # noqa: E402
from repro.fl import (init_cnn_spec, model_bytes,  # noqa: E402
                      sample_system_telemetry)
from repro.fl.models import apply_spec  # noqa: E402

SPEC = [("fc", 64, 128), ("fc", 128, 64), ("fc", 64, 10)]
MODES = ("loop", "batched", "fused", "scanned")


def make_setup(num_clients: int, shard: int, seed: int = 0):
    """Homogeneous clients with equal-size synthetic shards (stackable)."""
    rng = np.random.default_rng(seed)
    xs = jnp.asarray(rng.normal(size=(num_clients, shard, 64)), jnp.float32)
    ys = jnp.asarray(rng.integers(0, 10, size=(num_clients, shard)))
    params = init_cnn_spec(jax.random.PRNGKey(seed), SPEC)
    tel = sample_system_telemetry(
        num_clients, [model_bytes(params)] * num_clients,
        [shard] * num_clients, [1.0] * num_clients, seed=seed)

    def _loss(p, x, y):
        logits = apply_spec(p, SPEC, x)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return jnp.mean(logz - gold)

    def _sgd_step(p, x, y):
        loss, g = jax.value_and_grad(_loss)(p, x, y)
        return jax.tree_util.tree_map(lambda w, gw: w - 0.05 * gw, p, g), loss

    step = jax.jit(_sgd_step)

    def local_train(p, idx, rng_):
        del rng_
        return step(p, xs[idx], ys[idx])

    batched_train = jax.jit(make_batched_train_fn(_sgd_step, (xs, ys)))
    return params, tel, local_train, batched_train


def run_mode(mode: str, params, tel, local_train, batched_train, *,
             rounds: int, use_kernel: bool, seed: int = 0,
             rounds_per_dispatch: int = 8):
    cfg = ProtocolConfig(
        scheme="feddd", rounds=rounds, a_server=0.6, h=5, seed=seed,
        batched=(mode != "loop"), allocator="jax",
        mesh=(True if mode == "sharded" else None),
        rounds_per_dispatch=(rounds_per_dispatch if mode == "scanned"
                             else 1),
        selection=SelectionConfig(use_kernel=use_kernel))
    server = FedDDServer(params, cfg, tel)
    t0 = time.perf_counter()
    if mode in ("fused", "scanned", "sharded"):
        res = server.run(batched_train_fn=batched_train)
    else:
        res = server.run(local_train)
    jax.block_until_ready(jax.tree_util.tree_leaves(res.global_params))
    return res, time.perf_counter() - t0


def run_ab(clients: int, rounds: int, *, use_kernel: bool = False,
           rounds_per_dispatch: int = 8, modes=MODES, seed: int = 0):
    """Time every mode (warm-up run first so compiles — including both
    scan chunk lengths — land outside the timed region).  Returns
    ``(rows, results)`` with ``results[mode] = (RunResult, wall, rps)``.

    ``rounds_per_dispatch`` is clamped to the EFFECTIVE chunk length
    ``min(K, rounds)`` so rows/JSON never label a configuration that was
    not actually executed (the protocol clamps trailing chunks the same
    way); K < 2 is rejected — rounds_per_dispatch=1 is per-round
    dispatch, which is the ``fused`` mode, not ``scanned``.
    """
    rounds_per_dispatch = min(rounds_per_dispatch, rounds)
    if "scanned" in modes and rounds_per_dispatch < 2:
        raise ValueError(
            "scanned mode needs an effective rounds_per_dispatch >= 2 "
            "(K=1 IS the per-round fused path)")
    setup = make_setup(clients, 32, seed=seed)
    kw = dict(rounds=rounds, use_kernel=use_kernel, seed=seed,
              rounds_per_dispatch=rounds_per_dispatch)
    results = {}
    for mode in modes:
        run_mode(mode, *setup, **kw)                       # warm-up
        res, wall = run_mode(mode, *setup, **kw)
        results[mode] = (res, wall, rounds / wall)

    base_mode = "loop" if "loop" in results else modes[0]
    base = results[base_mode][2]
    g_base = jax.tree_util.tree_leaves(results[base_mode][0].global_params)
    rows = []
    for mode, (res, wall, rps) in results.items():
        dev = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            g_base, jax.tree_util.tree_leaves(res.global_params)))
        extra = (f" rounds_per_dispatch={rounds_per_dispatch}"
                 if mode == "scanned" else "")
        rows.append(csv_row(
            f"fed_round_{mode}", wall / rounds,
            f"rounds_per_sec={rps:.2f} speedup_vs_{base_mode}="
            f"{rps / base:.2f}x max_dev_vs_{base_mode}={dev:.1e} "
            f"clients={clients}{extra}"))
    return rows, results


def _digest(res) -> str:
    """Bit-level digest of a run's LEARNING state: global params + the
    per-round losses / upload fractions / participation.

    The dropout rates are deliberately excluded: XLA compiles the
    Eq. (9)-(11) golden-section search per program, and even fenced with
    optimization_barrier the search's last float32 bit is context
    sensitive for some loss inputs (sequential dispatch vs scan-inlined
    are different XLA programs).  The learning state must match exactly;
    the rates are asserted to within one f32 ulp separately.
    """
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(res.global_params):
        h.update(np.asarray(leaf).tobytes())
    for r in res.history:
        h.update(np.asarray(
            [r.mean_loss, r.uploaded_fraction,
             float(r.participants)]).tobytes())
    return h.hexdigest()


def smoke(clients: int = 8, rounds: int = 2, rounds_per_dispatch: int = 2
          ) -> int:
    """CI gate: scanned dispatch must reproduce sequential dispatch —
    learning-state digests exactly, allocator outputs within 2 float32
    ulps at unit scale on the [0, 1] dropout domain.  No perf
    thresholds."""
    setup = make_setup(clients, 32)
    kw = dict(rounds=rounds, use_kernel=False,
              rounds_per_dispatch=rounds_per_dispatch)
    seq, _ = run_mode("fused", *setup, **kw)
    scan, _ = run_mode("scanned", *setup, **kw)
    d_seq, d_scan = _digest(seq), _digest(scan)
    print(f"sequential digest: {d_seq}")
    print(f"scanned    digest: {d_scan}")
    if d_seq != d_scan:
        print("# FAIL: scanned dispatch diverged from sequential "
              "(params/losses/participation)", file=sys.stderr)
        return 1
    # The search's context sensitivity is an ABSOLUTE perturbation (one
    # ulp of the t_star bracket propagated through the knapsack), so the
    # gate is absolute on the [0, 1] dropout domain: 2 ulps at unit scale.
    unit_ulp = float(np.spacing(np.float32(1.0)))          # 1.19e-07
    rate_dev = max(float(np.max(np.abs(a.dropout_rates - b.dropout_rates)))
                   for a, b in zip(seq.history, scan.history))
    time_dev = max(abs(a.sim_time - b.sim_time) / max(a.sim_time, 1e-9)
                   for a, b in zip(seq.history, scan.history))
    print(f"# allocator max dev: rates={rate_dev / unit_ulp:.1f} f32 ulps "
          f"at unit scale ({rate_dev:.2e}), Eq.(12) rel dev={time_dev:.2e}")
    if rate_dev > 2 * unit_ulp or time_dev > 1e-6:
        print("# FAIL: allocator drifted beyond 2 unit-scale f32 ulps",
              file=sys.stderr)
        return 1
    print(f"# OK: rounds_per_dispatch={rounds_per_dispatch} matches "
          f"per-round dispatch ({clients} clients, {rounds} rounds)")
    return 0


def sharded_ab(clients_list=(256, 1024), rounds: int = 6) -> dict:
    """Sharded-vs-fused scaling curve on the VISIBLE device mesh, in this
    process.

    Runs the same homogeneous FedDD simulation as the per-round ``fused``
    mode and the client-sharded ``sharded`` mode (ProtocolConfig mesh=True
    -> ShardedRoundEngine over every visible device) and reports
    rounds/sec, the sharded speedup, and the scaling efficiency
    (speedup / devices).  On a CPU run the devices are whatever
    ``--xla_force_host_platform_device_count`` made (virtual devices
    sharing the host's cores); on a 1-device process the sharded mode
    degenerates to shard_map overhead measurement.

    ``platform`` / ``device_kind`` name the devices measured, and
    ``physical_parallelism`` is true only for accelerator devices: CPU
    devices, virtual or not, are threads of one host process, and the
    acceptance gate only binds on real parallel hardware.
    """
    import os
    devices = jax.devices()
    out = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "devices": len(devices),
        "cpu_count": os.cpu_count() or 1,
        "physical_parallelism": devices[0].platform != "cpu",
        "clients": {},
    }
    for c in clients_list:
        setup = make_setup(c, 8)
        kw = dict(rounds=rounds, use_kernel=False, rounds_per_dispatch=8)
        per = {}
        for mode in ("fused", "sharded"):
            run_mode(mode, *setup, **{**kw, "rounds": 2})       # warm-up
            _, wall = run_mode(mode, *setup, **kw)
            per[mode] = rounds / wall
        speedup = per["sharded"] / per["fused"]
        out["clients"][str(c)] = {
            "fused_rounds_per_sec": per["fused"],
            "sharded_rounds_per_sec": per["sharded"],
            "sharded_speedup": speedup,
            "scaling_efficiency": speedup / len(devices),
        }
    return out


def bench_json(out_dir: Path, *, clients=(16, 64), rounds: int = 6,
               rounds_per_dispatch: int = 8,
               sharded_clients=(256, 1024)) -> Path:
    """Machine-readable perf trajectory: rounds/sec per execution path at
    each fleet size -> results/BENCH_round_engine.json (CI artifact, the
    regression baseline future PRs compare against).  The ``sharded``
    section is the client-sharded scaling curve over the devices this
    process sees (:func:`sharded_ab`); no child process is started, so
    the run never needs a device its own process already holds."""
    rounds_per_dispatch = min(rounds_per_dispatch, rounds)  # effective K
    payload = {
        "bench": "round_engine",
        "backend": jax.default_backend(),
        "jax": jax.__version__,
        "rounds": rounds,
        "rounds_per_dispatch": rounds_per_dispatch,
        "clients": {},
    }
    for c in clients:
        _, results = run_ab(c, rounds,
                            rounds_per_dispatch=rounds_per_dispatch)
        payload["clients"][str(c)] = {
            mode: {"rounds_per_sec": rps,
                   "sec_per_round": wall / rounds}
            for mode, (_, wall, rps) in results.items()
        }
    payload["sharded"] = sharded_ab(sharded_clients, rounds)
    biggest = str(max(clients))
    per = payload["clients"][biggest]
    speedup = (per["scanned"]["rounds_per_sec"]
               / per["batched"]["rounds_per_sec"])
    scan_ge_fused = all(
        modes["scanned"]["rounds_per_sec"] >= modes["fused"]["rounds_per_sec"]
        for modes in payload["clients"].values())
    sh = payload["sharded"]
    sh_big = sh["clients"][str(max(sharded_clients))]
    sharded_ok = (sh_big["sharded_speedup"] >= 3.0
                  if sh["physical_parallelism"] else None)
    payload["acceptance"] = {
        "scanned_vs_batched_at_max_clients": speedup,
        "target": 1.5,
        "scanned_ge_fused_at_every_client_count": bool(scan_ge_fused),
        "sharded_speedup_at_max_clients": sh_big["sharded_speedup"],
        "sharded_target": 3.0,
        "sharded_gate_binding": sh["physical_parallelism"],
        "sharded_pass": sharded_ok,
        "pass": bool(speedup >= 1.5 and scan_ge_fused
                     and (sharded_ok is not False)),
    }
    return write_json(out_dir, "BENCH_round_engine.json", payload)


def _write_csv(out_dir: Path, rows) -> None:
    write_table(out_dir, "perf_federated.csv",
                ["name,us_per_round,derived"] + list(rows))


def run(full: bool = False, out_dir: Path | None = None):
    """benchmarks/run.py entry: reduced A/B over the rounds-per-dispatch
    axis, written to results/perf_federated.csv."""
    clients = 64 if full else 8
    rounds = 10 if full else 4
    k = 8 if full else 2
    rows, _ = run_ab(clients, rounds, rounds_per_dispatch=k)
    if out_dir:
        _write_csv(out_dir, rows)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=64)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--shard", type=int, default=32)
    ap.add_argument("--rounds-per-dispatch", type=int, default=8,
                    help="chunk length K of the scanned mode (lax.scan "
                         "over K rounds per device dispatch)")
    ap.add_argument("--use-kernel", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI parity gate: 8 clients, 2 rounds, K=2; "
                         "asserts scanned == sequential digests")
    ap.add_argument("--json", action="store_true",
                    help="write results/BENCH_round_engine.json "
                         "(rounds/sec per path at 16/64 clients + the "
                         "sharded scaling curve on the visible devices)")
    ap.add_argument("--sharded", action="store_true",
                    help="print the sharded-vs-fused scaling curve on the "
                         "VISIBLE devices (run under XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
    args = ap.parse_args()

    if args.smoke:
        sys.exit(smoke())
    if args.sharded:
        import json as _json
        print(_json.dumps(sharded_ab((args.clients,), rounds=args.rounds),
                          indent=1))
        return
    out_dir = Path(__file__).resolve().parents[1] / "results"
    if args.json:
        out = bench_json(out_dir)
        print(out.read_text())
        return

    rows, results = run_ab(args.clients, args.rounds,
                           use_kernel=args.use_kernel,
                           rounds_per_dispatch=args.rounds_per_dispatch)
    for r in rows:
        print(r)
    _write_csv(out_dir, rows)
    base = results["loop"][2]
    speedup = results["batched"][2] / base
    scan_gain = results["scanned"][2] / results["batched"][2]
    k_eff = min(args.rounds_per_dispatch, args.rounds)
    print(f"# batched engine speedup at {args.clients} clients: "
          f"{speedup:.2f}x (target >= 3x)")
    print(f"# scanned (K={k_eff}) vs per-round engine: "
          f"{scan_gain:.2f}x (target >= 1.5x)")
    failed = False
    if speedup < 3.0:
        print("# FAIL: batched below the 3x acceptance target",
              file=sys.stderr)
        failed = True
    if scan_gain < 1.5:
        print("# FAIL: scanned below the 1.5x acceptance target",
              file=sys.stderr)
        failed = True
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
