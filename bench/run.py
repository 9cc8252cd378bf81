#!/usr/bin/env python3
"""One run of one benchmark cell of the FedDD round engines on the chip.

    python3 bench/run.py --workload vgg_full_128.scanned --seed 7 \\
        --seconds 10 --trace 0

Everything is found by name: the cell in ``BENCHMARK.json``, its
configuration (``bench/configs/<config>.json``), its traffic mix
(``bench/traffic/<traffic>.json``), its correctness limits
(``bench/limits/<cell>.json``), its plain reference
(``bench/references/<reference>.py``) and each per-layer metric's reader
(``bench/metrics/<metric>.py``).

A run: build the fleet from the seed (weights in one jitted call, data,
telemetry), hand it to one ``FedDDServer``; set-up drives that server
through its first rounds from the seed in two ``run`` calls, of 1 and of
``h`` rounds (``check_calls``: this compiles every program the window
uses), then one more call of ``h`` rounds gives the pace; the window is
ONE ``run`` call of as many rounds as fill ``--seconds`` at that pace (a
multiple of ``h``), ended with ``block_until_ready`` on the global and
every client's parameters.  Then the program is freed, the plain
reference follows the same first rounds, and the readings of both are
compared (``bench/compare.py``).

``--trace 0`` prints the end-to-end metrics; ``--trace 1`` traces the
window with the profiler (and ``repro.obs`` host spans) and prints the
per-layer metrics.  The last line of standard output is one JSON object;
the numbers compared, each beside its limit, are the last lines of
standard error and the last key of that object.  Without a TPU, or with
fewer chips than the cell asks for, the run exits non-zero and prints no
result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
CACHE = ROOT / ".bench_cache"
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import compare, counts, fleet, trace as trace_mod  # noqa: E402


# --------------------------------------------------------------- the cell

def load_cell(name: str) -> dict:
    """The cell's entry of BENCHMARK.json with its configuration, traffic,
    limits and per-layer metric names."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: "
                         f"{sorted(cells)}")
    cell = dict(cells[name])
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cell["config_data"] = json.loads((ROOT / conf["file"]).read_text())
    cell["traffic_data"] = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    cell["limits"] = json.loads(
        (BENCH / "limits" / f"{name}.json").read_text())
    cell["per_layer"] = [m["name"] for m in spec["per_layer"]
                         if name in m.get("workloads", [name])]
    cell["end_to_end"] = {m["name"]: m["unit"] for m in spec["end_to_end"]
                          if name in m.get("workloads", [name])}
    cell["per_layer_units"] = {m["name"]: m["unit"]
                               for m in spec["per_layer"]}
    return cell


def check_device(chips: int) -> dict:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: no TPU (platform {devs[0].platform!r}); the "
                 "benchmark measures the chip only")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, {len(devs)} visible")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def enable_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    holding every program, so only a checkout's first run compiles."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", 4 << 30)


def check_calls(conf: dict) -> list:
    """The rounds of each checked ``run`` call: one round (every channel
    uploads, so its update is local training and the Eq. (4) mean alone),
    then ``h`` rounds, whose last is the Eq. (6) full broadcast (each call
    counts its rounds from 1)."""
    return [1, conf["h"]]


# ---------------------------------------------------------------- the fleet

def build_setup(conf: dict, traffic: dict, seed: int) -> dict:
    """Sizes, data, telemetry and layer lists from the configuration and
    the seed: what the program is handed and the reference rebuilds."""
    global_layers = fleet.vgg_layers(conf["global"]["conv"],
                                     conf["global"]["fc"])
    client_layers = [fleet.vgg_layers(g["conv"], g["fc"])
                     for g in conf["fleet"] for _ in range(g["clients"])]
    n, s = len(client_layers), conf["samples_per_client"]
    # one data set per configuration: client data a jitted trainer closes
    # over is compiled into the round program, so data drawn anew per seed
    # would compile anew per seed
    xs, ys, xte, yte = fleet.client_shards(conf["data_seed"], n, s,
                                           traffic["eval_samples"])
    tel = fleet.telemetry(seed, [fleet.param_bytes(l) for l in client_layers],
                          [s] * n, conf["local_epochs"])
    return {"seed": seed, "global_layers": global_layers,
            "client_layers": client_layers, "xs": xs, "ys": ys,
            "xte": xte, "yte": yte, "telemetry": tel, "lr": conf["lr"],
            "batch": conf["batch_size"], "epochs": conf["local_epochs"],
            "a_server": conf["a_server"], "d_max": conf["d_max"],
            "delta": conf["delta"], "h": conf["h"],
            "order": "stored" if traffic["train"] == "fused" else "permuted",
            "eval": traffic["eval_samples"] > 0}


class Program:
    """The system under test: one FedDDServer on the cell's
    ProtocolConfig, with the training and eval callables a user hands it.
    ``call(rounds)`` is one ``FedDDServer.run``, ended with
    ``block_until_ready`` on the global and every client's parameters."""

    def __init__(self, setup: dict, traffic: dict):
        from repro.core import FedDDServer, ProtocolConfig
        from repro.core.allocation import ClientTelemetry
        from repro.core.round_engine import make_batched_train_fn
        from repro.fl.models import (apply_spec, make_eval_fn,
                                     make_local_train_fn)
        kinds = []
        for l in setup["client_layers"]:
            if l not in kinds:
                kinds.append(l)
        self.g0, subs = fleet.make_weights(setup["seed"],
                                           setup["global_layers"], kinds)
        # the program may donate what it is handed: keep the origin apart
        self.origin = jax.tree_util.tree_map(jnp.copy, self.g0)
        self.cfg = ProtocolConfig(
            scheme="feddd", a_server=setup["a_server"],
            d_max=setup["d_max"], delta=setup["delta"], h=setup["h"],
            seed=fleet.seed32(setup["seed"]),
            allocator=traffic["allocator"],
            rounds_per_dispatch=traffic["rounds_per_dispatch"])
        tel = ClientTelemetry(**setup["telemetry"])
        n = len(setup["client_layers"])
        self.eval_fn = None
        if setup["eval"]:
            self.eval_fn = make_eval_fn(
                setup["global_layers"],
                types.SimpleNamespace(x=setup["xte"], y=setup["yte"]),
                batch_size=512)
        if traffic["train"] == "fused":
            step = fleet.make_client_step(
                lambda p, x: apply_spec(p, setup["global_layers"], x),
                setup["batch"], setup["lr"], setup["epochs"])
            xs, ys = jnp.asarray(setup["xs"]), jnp.asarray(setup["ys"])
            self.train = jax.jit(make_batched_train_fn(step, (xs, ys)))
            self.server = FedDDServer(self.g0, self.cfg, tel)
            self.local = None
        else:
            s = setup["xs"].shape[1]
            ds = types.SimpleNamespace(
                x=setup["xs"].reshape(n * s, *setup["xs"].shape[2:]),
                y=setup["ys"].reshape(n * s))
            parts = np.arange(n * s).reshape(n, s)
            fns = [make_local_train_fn(l, ds, parts, lr=setup["lr"],
                                       batch_size=setup["batch"],
                                       local_epochs=setup["epochs"])
                   for l in kinds]
            which = [kinds.index(l) for l in setup["client_layers"]]
            self.local = lambda p, i, rng: fns[which[i]](p, i, rng)
            self.train = None
            self.server = FedDDServer(
                self.g0, self.cfg, tel,
                client_params=[subs[w] for w in which])

    def call(self, rounds: int):
        res = self.server.run(self.local, self.eval_fn, rounds=rounds,
                              batched_train_fn=self.train)
        jax.block_until_ready(jax.tree_util.tree_leaves(
            [self.server.global_params,
             [c.params for c in self.server.clients]]))
        return res

    def first_update(self) -> dict:
        """Leaf -> the global model's change from the initial weights."""
        g = jax.device_get(self.server.global_params)
        o = jax.device_get(self.origin)
        return {f"{nm}.{k}": np.asarray(g[nm][k], np.float32) - o[nm][k]
                for nm in g for k in g[nm]}

    def readings(self, first_update: dict, history: list) -> dict:
        """What the comparison takes from the checked calls: the first
        call's update, every checked round's rates, the change norms
        after the last checked call."""
        return {"first_update": first_update,
                "rates": np.stack([np.asarray(r.dropout_rates, np.float64)
                                   for r in history]),
                "global_change": compare.change_norms(
                    self.server.global_params, self.origin),
                "clients_change": compare.clients_change_norms(
                    [c.params for c in self.server.clients], self.origin)}

    def checked_calls(self, calls) -> dict:
        """Drive the server through the checked calls from the seed;
        returns the readings."""
        history, first = [], None
        for c in calls:
            history += self.call(c).history
            if first is None:
                first = self.first_update()
        return self.readings(first, history)


# ------------------------------------------------------------------ a run

class CompileCounter:
    """Counts the programs JAX compiles or loads from its persistent cache
    inside the ``with`` block."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.count = 0

    def _event(self, name, *_a, **_k):
        self.count += name in self.EVENTS

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._event)


def peak_bytes(chips: int) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


def run_cell(cell: dict, seed: int, seconds: float, traced: bool,
             device: dict, t_start: float = T_START) -> dict:
    """Set-up, window, reference and comparison of one run; returns the
    result line as a dict."""
    conf, traffic = cell["config_data"], cell["traffic_data"]
    chips = cell["chips"]
    clock = {"start": time.perf_counter() - t_start}
    scopes = trace_mod.ScopeMap() if traced else None
    setup = build_setup(conf, traffic, seed)
    prog = Program(setup, traffic)
    clock["fleet"] = time.perf_counter() - t_start
    # the checked calls compile every program the window runs; one more
    # call of ``h`` rounds gives the pace, split into its rounds and what
    # the call costs besides them (stacking, unstacking)
    readings = prog.checked_calls(check_calls(conf))
    clock["checked_calls"] = time.perf_counter() - t_start
    step = conf["h"]
    t0 = time.perf_counter()
    hist = prog.call(step).history
    call_s = time.perf_counter() - t0
    per_round = sum(r.host_wall_time for r in hist) / len(hist)
    fixed = max(call_s - per_round * len(hist), 0.0)
    rounds = max(step, step * round((seconds - fixed) / per_round / step))
    if scopes is not None:
        scopes.stop()

    tdir = CACHE / "trace" / cell["name"]
    if traced:
        from repro.obs import ObsConfig
        prog.server.cfg = dataclasses.replace(prog.server.cfg,
                                              obs=ObsConfig(trace=True))
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # Python calls would swamp the host
        opts.host_tracer_level = 1        # user annotations: the obs spans
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    t0 = time.perf_counter()
    with CompileCounter() as counter, \
            jax.profiler.TraceAnnotation(trace_mod.WINDOW):
        res = prog.call(rounds)
    wall = time.perf_counter() - t0
    if traced:
        jax.profiler.stop_trace()
    losses = np.asarray([r.mean_loss for r in res.history])
    finite = all(bool(jnp.all(jnp.isfinite(x))) for x in
                 jax.tree_util.tree_leaves(prog.server.global_params))
    failed = rounds if not finite else int(np.sum(~np.isfinite(losses)))
    peak = peak_bytes(chips)
    window = {"rounds": len(res.history), "seconds": wall,
              "compiles": counter.count, "pace_s_per_round": per_round,
              "pace_s_per_call": fixed, "setup_clock_s": clock}
    del res, prog, hist
    gc.collect()

    if traced:
        t0 = time.perf_counter()
        red = trace_mod.reduce(trace_mod.load(tdir, scopes.modules),
                               list(range(chips)))
        window["trace_read_s"] = time.perf_counter() - t0
        shutil.rmtree(tdir, ignore_errors=True)
        ctx = {"trace": red, "rounds": rounds, "chips": chips,
               "setup": setup, "traffic": traffic,
               "peaks": counts.peaks(device["kind"])}
        metrics = {}
        for name in cell["per_layer"]:
            reader = importlib.import_module(f"bench.metrics.{name}")
            value = reader.read(ctx)
            if value is not None:
                metrics[name] = {"value": value,
                                 "unit": cell["per_layer_units"][name]}
    else:
        metrics = {"rounds_per_s": {"value": rounds / wall,
                                    "unit": "rounds/s"},
                   "peak_hbm_gb": {"value": peak / 1e9, "unit": "GB"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
        metrics = {k: v for k, v in metrics.items()
                   if k in cell["end_to_end"]}

    t0 = time.perf_counter()
    ref = importlib.import_module(
        f"bench.references.{conf['reference']}").run(
        setup, check_calls(conf))
    window["reference_s"] = time.perf_counter() - t0
    numbers = compare.gaps(readings, ref,
                           setup["telemetry"]["model_bytes"])
    checks = compare.judge(numbers, cell["limits"])
    out = {"correct": failed == 0 and all(c["ok"] for c in checks),
           "attempted": rounds, "failed": failed, "metrics": metrics,
           "device": dict(device, memory_peak_bytes=peak),
           "window": window}
    if traced:
        out["device"].update(busy_s=red["busy_s"],
                             window_s=red["window_s"])
        out["breakdown"] = {"device_ops": trace_mod.top(red["ops"]),
                            "idle_gaps": trace_mod.top(red["idle_gaps"])}
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"bench: the program (src/repro) is not in {ROOT}")
    device = check_device(cell["chips"])
    enable_cache()
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), device)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
