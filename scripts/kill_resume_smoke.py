#!/usr/bin/env python
"""Kill-and-resume smoke driver (CI survivability lane).

Runs the same faulty, obs-logged simulation three times in child
processes:

* ``full``   — uninterrupted reference run;
* ``crash``  — checkpointing every round, SIGKILL'd (uncatchable)
  mid-round 4 via its own eval hook;
* ``resume`` — restarted from the last atomic snapshot the crashed
  process managed to write.

The run digest (sha256 over the event trace, per-round records, dropout
rates, and final global params) of ``resume`` must equal ``full``
byte-for-byte — the crash-resume contract of
``repro.checkpoint.run_state`` (pinned in tests/test_resume.py; this
script is the CI smoke that also leaves the artifacts behind).

::

    PYTHONPATH=src python scripts/kill_resume_smoke.py \
        [--out-dir results/kill_resume]

Writes ``full.jsonl`` / ``crash.jsonl`` / ``resume.jsonl`` run logs, the
surviving ``ck.npz`` snapshot (+ sidecar), and a ``summary.json`` with
the digests and verdict into the output dir (uploaded as a CI
artifact); exits non-zero on any contract violation.

CI only: the children run with ``JAX_PLATFORMS=cpu`` unless the caller
sets that variable, because the digests are the CPU lane's contract.
This script is not a chip entry point (``chip_smoke.py`` is), and no
entry point pins its children, or itself, to the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
N, ROUNDS, CRASH_AT_EVAL = 5, 6, 4


def _child(mode: str, ckpt_path: str, log_path: str) -> None:
    """One simulation run; prints the run digest (never returns in
    ``crash`` mode — the process SIGKILLs itself mid-round)."""
    import hashlib

    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.core.allocation import ClientTelemetry
    from repro.obs import ObsConfig
    from repro.sim import (CellOutageModel, FaultConfig, OutageConfig,
                           RandomFaults, SimConfig, run_sim)

    def params():
        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        return {"fc0": {"w": jax.random.normal(k1, (20, 12)),
                        "b": jnp.zeros(12)},
                "fc1": {"w": jax.random.normal(k2, (12, 5)),
                        "b": jnp.zeros(5)}}

    def tel():
        rng = np.random.default_rng(0)
        nbytes = float(sum(l.size * l.dtype.itemsize
                           for l in jax.tree_util.tree_leaves(params())))
        return ClientTelemetry(
            model_bytes=np.full(N, nbytes),
            uplink_rate=rng.uniform(1e3, 5e3, N),
            downlink_rate=rng.uniform(5e3, 2e4, N),
            compute_latency=rng.uniform(1.0, 5.0, N),
            num_samples=rng.integers(10, 50, N).astype(float),
            label_coverage=rng.uniform(0.5, 1.0, N),
            train_loss=np.ones(N))

    def ltf(p, idx, key):
        return (jax.tree_util.tree_map(
            lambda x: x * 0.99 + 0.01 * jax.random.normal(key, x.shape),
            p), 1.0 / (idx + 1.0))

    calls = []

    def eval_fn(p):
        calls.append(1)
        if mode == "crash" and len(calls) == CRASH_AT_EVAL:
            os.kill(os.getpid(), signal.SIGKILL)
        return {"probe": float(jnp.sum(p["fc1"]["b"]))}

    faults = CellOutageModel(
        N, OutageConfig(cells=2, p_out=0.3, p_back=0.5, seed=3),
        inner=RandomFaults(FaultConfig(crash_rate=0.15, loss_rate=0.1,
                                       seed=5)))
    kw = dict(sim=SimConfig(policy="sync"), faults=faults, rounds=ROUNDS,
              a_server=0.6, h=2, seed=0,
              obs=ObsConfig(enabled=True, jsonl_path=log_path))
    if mode in ("crash", "resume"):
        kw.update(checkpoint_every=1, checkpoint_path=ckpt_path)
    if mode == "resume":
        kw.update(resume_from=ckpt_path)

    res = run_sim("feddd", params(), tel(), ltf, eval_fn, **kw)

    h = hashlib.sha256()
    times = np.asarray([e[0] for e in res.event_trace])
    h.update(times.tobytes())
    h.update(",".join(f"{e[1]}:{e[2]}" for e in res.event_trace).encode())
    rec = np.asarray([[r.sim_time, r.mean_loss, r.participants,
                       r.survivors, r.retries, r.abandoned_bytes,
                       float(r.skipped)] for r in res.history])
    h.update(rec.tobytes())
    h.update(np.concatenate([np.asarray(r.dropout_rates)
                             for r in res.history]).tobytes())
    for leaf in jax.tree_util.tree_leaves(res.global_params):
        h.update(np.asarray(leaf).tobytes())
    print(h.hexdigest())


def _spawn(mode: str, out_dir: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")   # CI-only setting, see above
    env["PYTHONPATH"] = (str(REPO / "src") + os.pathsep
                         + env.get("PYTHONPATH", "")).rstrip(os.pathsep)
    return subprocess.run(
        [sys.executable, __file__, "--child", mode,
         "--out-dir", str(out_dir)],
        capture_output=True, text=True, env=env, check=False)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out-dir", default=str(REPO / "results"
                                             / "kill_resume"))
    ap.add_argument("--child", metavar="MODE",
                    choices=("full", "crash", "resume"),
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    out_dir = Path(args.out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    ckpt = out_dir / "ck.npz"

    if args.child:
        _child(args.child, str(ckpt), str(out_dir / f"{args.child}.jsonl"))
        return 0

    failures = []
    full = _spawn("full", out_dir)
    if full.returncode != 0:
        print(full.stderr[-2000:], file=sys.stderr)
        failures.append("full run failed")
    crashed = _spawn("crash", out_dir)
    if crashed.returncode != -signal.SIGKILL:
        failures.append(f"crash child exited {crashed.returncode}, "
                        "expected SIGKILL (-9)")
    if not ckpt.exists():
        failures.append("crashed run left no snapshot behind")
    resumed = _spawn("resume", out_dir)
    if resumed.returncode != 0:
        print(resumed.stderr[-2000:], file=sys.stderr)
        failures.append("resume run failed")

    d_full = full.stdout.strip()
    d_resume = resumed.stdout.strip()
    if not failures and (len(d_full) != 64 or d_full != d_resume):
        failures.append("resumed digest differs from uninterrupted run")
    summary = {
        "rounds": ROUNDS, "clients": N, "crash_at_eval": CRASH_AT_EVAL,
        "digest_full": d_full, "digest_resume": d_resume,
        "ok": not failures, "failures": failures,
    }
    (out_dir / "summary.json").write_text(json.dumps(summary, indent=2))
    print(json.dumps(summary, indent=2))
    if failures:
        return 1
    print("kill-and-resume smoke OK: resumed run is bit-identical")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
