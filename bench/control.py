#!/usr/bin/env python3
"""Readings that set a cell's correctness limits: the control and the
planted faults.  The benchmark's own runs never run this.

    python3 bench/control.py --workload vgg_full_128.scanned \\
        --seeds 11,12,13 --mode control

Modes (each prints, per seed, every number ``bench/run.py`` compares):

* ``control`` -- the plain reference put in the program's place and
  computed in bfloat16, the precision below the configuration's float32,
  against the reference at the configuration's precision;
* ``program`` -- the program as the cell runs it (its checked calls,
  no window), the lower reading;
* ``frozen`` -- the program with the FedDD aggregation returning the
  previous global: a round that leaves the state unchanged;
* ``half_clients`` -- the program's Eq. (4) with the second half of the
  fleet left out, the mean taken over the rest;
* ``altered_rates`` -- the program's dropout-rate LP returning client 0's
  rate moved by 0.2 (down from above 0.4, else up): an answer altered
  where it is produced;
* ``no_exchange`` -- on a client-sharded mesh, the Eq. (4) partials not
  summed across chips: each chip's mean over its own clients alone.

A fault covers every engine a cell can run: the stacked, grouped and
client-sharded Eq. (4) steps alike.

A fault is planted by replacing the program's function for the whole
process, before anything is traced.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import compare, run  # noqa: E402

MODES = ("control", "program", "frozen", "half_clients", "altered_rates",
         "no_exchange")


def _alter(rate):
    """A rate moved by 0.2 towards the middle of [0, 0.8]."""
    import jax.numpy as jnp
    return jnp.where(rate > 0.4, rate - 0.2, rate + 0.2)


def plant(mode: str, set_attr=setattr) -> None:
    """Replace the program's function that ``mode`` breaks, through
    ``set_attr(module, name, value)`` (a test passes its monkeypatch's)."""
    import jax.numpy as jnp
    from repro.core import aggregation, allocation, protocol, round_engine

    if mode == "frozen":
        set_attr(aggregation, "aggregate_sparse_stacked",
                 lambda params, masks, w, *, prev_global, **k: prev_global)
        set_attr(aggregation, "aggregate_sparse_grouped",
                 lambda *a, prev_global, **k: prev_global)
        set_attr(round_engine, "_leaf_sharded_reduce",
                 lambda num, den, gprev, dtype, **k:
                 (gprev.astype(dtype), jnp.float32(0.0)))
    elif mode == "no_exchange":
        set_attr(round_engine, "_leaf_sharded_reduce",
                 lambda num, den, gprev, dtype, **k:
                 (aggregation.finish_masked_mean(num, den, gprev, dtype),
                  jnp.float32(0.0)))
    elif mode == "half_clients":
        def halve(fn, pos):
            def wrapped(*a, **k):
                a = list(a)
                w = jnp.asarray(a[pos], jnp.float32)
                a[pos] = w * (jnp.arange(w.shape[0]) < w.shape[0] // 2)
                return fn(*a, **k)
            return wrapped
        set_attr(aggregation, "aggregate_sparse_stacked",
                 halve(aggregation.aggregate_sparse_stacked, 2))
        set_attr(aggregation, "aggregate_sparse_grouped",
                 halve(aggregation.aggregate_sparse_grouped, 3))
        set_attr(round_engine.ShardedRoundEngine, "step",   # self first
                 halve(round_engine.ShardedRoundEngine.step, 5))
    elif mode == "altered_rates":
        jax_lp = allocation.solve_dropout_rates_jax

        def lp_jax(*a, **k):
            d, t = jax_lp(*a, **k)
            return d.at[0].set(_alter(d[0])), t
        set_attr(allocation, "solve_dropout_rates_jax", lp_jax)
        np_lp = protocol.solve_dropout_rates_with

        def lp_np(*a, **k):
            res = np_lp(*a, **k)
            d = res.dropout_rates.copy()
            d[0] = float(_alter(d[0]))
            return type(res)(d, res.t_server, res.objective, res.feasible)
        set_attr(protocol, "solve_dropout_rates_with", lp_np)


@contextlib.contextmanager
def planted(mode: str):
    """``mode``'s fault planted inside the block, with no compiled program
    kept from before it or into what follows."""
    import jax
    undo = []

    def set_attr(obj, name, value):
        undo.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)
    plant(mode, set_attr)
    jax.clear_caches()
    try:
        yield
    finally:
        for obj, name, value in reversed(undo):
            setattr(obj, name, value)
        jax.clear_caches()


def readings(cell: dict, seed: int, mode: str) -> dict:
    conf, traffic = cell["config_data"], cell["traffic_data"]
    setup = run.build_setup(cell, seed)
    calls = run.check_calls(conf)
    reference = importlib.import_module(
        f"bench.references.{conf['reference']}")
    if mode == "control":
        got = reference.run(setup, calls, dtype="bfloat16",
                            precision="default")
    else:
        prog = run.Program(setup, traffic)
        got = prog.checked_calls(calls)
        del prog
        gc.collect()
    ref = reference.run(setup, calls)
    return compare.gaps(got, ref, setup["telemetry"]["model_bytes"]), {
        "global_leaf_gaps": compare.leaf_gaps(got["global_change"],
                                              ref["global_change"])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--mode", choices=MODES, required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    run.check_device(cell["chips"])
    run.enable_cache()
    if args.mode not in ("control", "program"):
        plant(args.mode)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        numbers, detail = readings(cell, seed, args.mode)
        print(json.dumps({"workload": args.workload, "mode": args.mode,
                          "seed": seed, "numbers": numbers,
                          "detail": detail,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
