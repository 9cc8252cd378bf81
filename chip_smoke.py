#!/usr/bin/env python3
"""Bring-up smoke run of the FedDD round engines on TPU.

    python chip_smoke.py              # one chip: phases 1-4
    python chip_smoke.py --chips 4    # four chips: the client-sharded engine

Drives the main path through the entry points a user calls
(``FedDDServer`` / ``run_scheme``) at the full width of the largest model
the repo supports: the paper's Table 3 full VGG (``HETERO_A_SPECS[0]``,
3.97 M parameters) on 32x32x3 inputs from ``make_dataset("cifar10")``,
weights drawn from a seed.

One chip:

1. device   -- the platform must be ``tpu``; anything else exits non-zero.
2. kernels  -- the three FedDD Pallas kernels (importance, sparse_agg,
               masked_merge) at every VGG leaf shape for a fleet of
               ``CLIENTS``, and sparse_agg again for ``TAIL_CLIENTS`` (a
               partial last client slab), compiled for the chip
               (``tpu_custom_call`` in the compiled text) and compared
               with their ``ref.py`` oracles.
3. fleet    -- ``CLIENTS`` full-VGG clients, fused local training
               (``make_batched_train_fn``) and ``allocator="jax"``: per-round
               dispatch, the same rounds scanned (``rounds_per_dispatch``),
               and scanned again with ``use_kernel=True``; then the fused
               engine against the per-client reference loop on a 4-client,
               2-round slice.  Each comparison runs in both ``MODES``:
               as a user runs it (FedDD's importance-ranked channel
               selection, default matmul precision), and as a control
               with the channels kept in a fixed order and float32
               matmuls over ``CONTROL_ROUNDS`` rounds, where the
               learned update is held to ``FIXED_MASK_TOL``.
4. ragged   -- the five hetero-a widths (paper Section V) through
               ``run_scheme`` on the grouped engine against the reference
               loop.

``--chips 4`` runs only the client-sharded engine (``ProtocolConfig(
mesh=4)``, dense and sparse collective) and the fused single-device
engine on the same fleet, to compare against, in both ``MODES``.

Each phase prints one line: wall seconds including compile, steady
seconds per round (a second run of the same configuration, ended with
``block_until_ready``) and the device's ``peak_bytes_in_use``.  Every
comparison prints its deviation beside its tolerance.  A failed
comparison fails its phase; any failed phase exits 1 with no result
line.  The last line of a run that passed is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import FedDDServer, ProtocolConfig, run_scheme  # noqa: E402
from repro.core.round_engine import make_batched_train_fn  # noqa: E402
from repro.core.selection import SelectionConfig  # noqa: E402
from repro.data import make_dataset  # noqa: E402
from repro.fl import (HETERO_A_SPECS, init_cnn_spec,  # noqa: E402
                      make_local_train_fn, model_bytes,
                      sample_system_telemetry)
from repro.fl.models import apply_spec  # noqa: E402
from repro.kernels.importance import ops as imp_ops  # noqa: E402
from repro.kernels.importance.ref import channel_importance_ref  # noqa: E402
from repro.kernels.masked_merge import ops as mm_ops  # noqa: E402
from repro.kernels.masked_merge.ref import masked_merge_ref  # noqa: E402
from repro.kernels.sparse_agg import ops as agg_ops  # noqa: E402
from repro.kernels.sparse_agg.ref import masked_weighted_sum_ref  # noqa: E402
from repro.launch.mesh import make_client_mesh  # noqa: E402

VGG = HETERO_A_SPECS[0]
CLIENTS = 32          # fleet size of phases 2 and 3
TAIL_CLIENTS = 13     # phase 2's sparse_agg fleet off the 8-client slab
SHARD = 64            # samples per client
BATCH = 32            # local minibatch: two SGD steps per round
LR = 0.05
ROUNDS = 4
K = 2                 # rounds per scanned dispatch
SEED = 0

# Kernel vs oracle, per element (the tolerances of tests/test_kernels.py
# for float32).  importance: the kernel sums the fan-in in 512-wide
# blocks, the oracle in one XLA reduce -- a reordered float32 sum of
# squares.  sparse_agg: the kernel reduces the client axis in slabs of 8.
# masked_merge: a select under a binary mask, so exact up to 1 ulp.
KERNEL_TOL = {"importance": (5e-5, 1e-5), "sparse_agg": (3e-5, 1e-4),
              "masked_merge": (1e-6, 0.0)}

# Learning state of two executions of the same rounds.  The compared
# programs are compiled differently (per-round vs scanned, vmapped vs
# per-client, kernel vs jnp, one device vs a mesh), so their float32 sums
# are reassociated.
#
# ROUND1_TOL -- round 1 runs before any dropout decision (D = 0, every
# channel uploads), so there the two differ only by that reassociation in
# local training and Eq. (4): its mean loss (relative) and the dropout
# rates it allocates (absolute, on [0, 1]) agree to 1e-5.
#
# STATE_TOL -- as a user runs it: FedDD's channel selection, and the
# default matmul precision, at which the chip rounds float32 conv and
# matmul operands to bfloat16.  A 1-ulp float32 difference between the two
# programs' weights flips the bfloat16 rounding of some operands, a 2^-9
# relative step that training carries into every later round, and
# FedDD's importance |dW * W / W_old| may swap near-tied channels on top.
# On the chip the learned updates of two such runs differ by 4-51% after
# 2-4 rounds, and by 4-44% with identical masks: up to ~2e-3 of the
# parameters.  Global and client parameters (relative L2) and per-round
# mean loss are held to 1e-2, which a path broken outright fails; a
# subtle fault is the control's to catch (FIXED_MASK_TOL).
#
# COUNT_TOL -- neither effect changes the number of uploaded channels,
# which only the allocated rates set: per-round uploaded fraction and
# dropout rates (absolute) agree to 1e-3.  A wrong keep count, a dropped
# or doubled client, or a wrong rate moves them by more.
#
# FIXED_MASK_TOL -- the control: channels kept in a fixed order
# (``scheme="ordered"``), so both runs keep the same channels, float32
# matmuls, so no bfloat16 rounding can flip, and CONTROL_ROUNDS rounds
# (the first with every channel, the second masked).  Only reassociation
# is left, and local training amplifies it: on the chip the mean losses
# of two float32 runs drift apart ~30x per round, and their learned
# updates differ by 4-7% after 4 rounds but by 9e-6 to 1.1e-4 after 2.
# The parameters are compared as the learned update (params minus the
# initial global), which a wrong aggregate moves in full -- a sparse_agg
# that skips one 8-client slab of 32 moved it by 0.5-0.6 on the chip --
# and are held, with the per-round mean loss, to 1e-3.
ROUND1_TOL = 1e-5
STATE_TOL = 1e-2
COUNT_TOL = 1e-3
FIXED_MASK_TOL = 1e-3
CONTROL_ROUNDS = 2

# (tag, channel selection, matmul precision) of each comparison: as a
# user runs it (timed), and the control
MODES = (("", SelectionConfig(), None),
         ("fixed-order f32 ", SelectionConfig(scheme="ordered"), "float32"))


class Failures(list):
    """Failed comparisons of the running phase (each printed as made)."""

    def check(self, what: str, dev: float, tol: float) -> None:
        ok = bool(np.isfinite(dev)) and dev <= tol
        print(f"  {'ok  ' if ok else 'FAIL'} {what}: dev={dev!r} "
              f"tol={tol!r}", flush=True)
        if not ok:
            self.append(what)


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def report(phase: str, wall: float, steady, failures) -> bool:
    print(json.dumps({"phase": phase, "ok": not failures,
                      "wall_s": wall, "steady_s_per_round": steady,
                      "peak_bytes_in_use": peak_bytes(),
                      "failed": list(failures)}), flush=True)
    return not failures


# ------------------------------------------------------------- comparisons

def rel_l2(a, b) -> float:
    """||a - b|| / ||b|| over all leaves of two pytrees (float64, host)."""
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    if len(la) != len(lb):
        raise ValueError(f"pytrees differ: {len(la)} vs {len(lb)} leaves")
    num = den = 0.0
    for x, y in zip(la, lb):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        num += float(np.sum((x - y) ** 2))
        den += float(np.sum(y ** 2))
    return (num / max(den, 1e-300)) ** 0.5


def excess(got, want, rtol: float, atol: float) -> float:
    """Largest violation of ``|got - want| <= atol + rtol * |want|`` as a
    multiple of the allowance: the check passes at <= 1."""
    worst = 0.0
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        err = np.abs(g - w)
        allow = atol + rtol * np.abs(w)
        ratio = np.where(allow > 0, err / np.where(allow > 0, allow, 1.0),
                         np.where(err > 0, np.inf, 0.0))
        worst = max(worst, float(np.max(ratio)))
    return worst


def learning_state(result, server=None):
    return {
        "global": result.global_params,
        "clients": (None if server is None
                    else [c.params for c in server.clients]),
        "loss": np.asarray([r.mean_loss for r in result.history]),
        "rates": np.stack([np.asarray(r.dropout_rates)
                           for r in result.history]),
        "uploaded": np.asarray([r.uploaded_fraction
                                for r in result.history]),
    }


def update(params, p0):
    """``params - p0`` in float64: the learned update of a global pytree,
    or of each client of a list of them."""
    if isinstance(params, list):
        return [update(p, p0) for p in params]
    return jax.tree_util.tree_map(
        lambda x, y: np.asarray(x, np.float64) - np.asarray(y, np.float64),
        params, p0)


def compare_states(fails: Failures, name: str, a, b, p0,
                   control: bool = False) -> None:
    """Check two runs' learning states against ROUND1_TOL, COUNT_TOL and
    STATE_TOL, or FIXED_MASK_TOL for the control mode (the reasons are
    with the constants).  ``p0``: the initial global params, the origin
    of the learned update."""
    def rel(x, y):
        return np.abs(x - y) / np.maximum(np.abs(y), 1e-12)

    fails.check(f"{name} round-1 mean loss rel",
                float(rel(a["loss"][0], b["loss"][0])), ROUND1_TOL)
    fails.check(f"{name} round-1 dropout rates abs",
                float(np.max(np.abs(a["rates"][0] - b["rates"][0]))),
                ROUND1_TOL)
    keys = ["global"] + ([] if a["clients"] is None else ["clients"])
    for key in keys:
        dev = rel_l2(update(a[key], p0), update(b[key], p0))
        if control:
            fails.check(f"{name} {key} update rel-L2", dev, FIXED_MASK_TOL)
        else:
            print(f"  info {name} {key} update rel-L2: {dev!r}", flush=True)
            fails.check(f"{name} {key} params rel-L2",
                        rel_l2(a[key], b[key]), STATE_TOL)
    fails.check(f"{name} mean loss rel",
                float(np.max(rel(a["loss"], b["loss"]))),
                FIXED_MASK_TOL if control else STATE_TOL)
    fails.check(f"{name} dropout rates abs",
                float(np.max(np.abs(a["rates"] - b["rates"]))), COUNT_TOL)
    fails.check(f"{name} uploaded fraction abs",
                float(np.max(np.abs(a["uploaded"] - b["uploaded"]))),
                COUNT_TOL)


# ------------------------------------------------------------------ fleet

def make_fleet(spec, clients: int, shard: int = SHARD, batch: int = BATCH,
               seed: int = SEED):
    """Global params, telemetry, stacked data and the per-client SGD step
    of a homogeneous fleet (each client: ``shard`` samples of the cifar10
    stand-in, ``shard // batch`` minibatch steps per round)."""
    train, _ = make_dataset("cifar10", num_train=clients * shard,
                            num_test=16, seed=seed)
    order = np.random.default_rng(seed).permutation(clients * shard)
    xs = jnp.asarray(train.x[order].reshape(clients, shard,
                                            *train.x.shape[1:]))
    ys = jnp.asarray(train.y[order].reshape(clients, shard))
    params = init_cnn_spec(jax.random.PRNGKey(seed), spec)
    tel = sample_system_telemetry(
        clients, [model_bytes(params)] * clients, [shard] * clients,
        [1.0] * clients, seed=seed)

    def loss_fn(p, x, y):
        logits = apply_spec(p, spec, x)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return jnp.mean(logz - gold)

    def client_step(p, x, y):
        """One local epoch: ``shard // batch`` SGD minibatch steps."""
        xb = x.reshape(-1, batch, *x.shape[1:])
        yb = y.reshape(-1, batch)

        def sgd(p, b):
            loss, g = jax.value_and_grad(loss_fn)(p, *b)
            return jax.tree_util.tree_map(lambda w, d: w - LR * d, p,
                                          g), loss

        p, losses = jax.lax.scan(sgd, p, (xb, yb))
        return p, jnp.mean(losses)

    return params, tel, xs, ys, client_step


def run_server(params, tel, cfg, *, batched_train_fn=None,
               local_train_fn=None):
    server = FedDDServer(params, cfg, tel)
    res = server.run(local_train_fn, batched_train_fn=batched_train_fn)
    jax.block_until_ready(jax.tree_util.tree_leaves(
        [res.global_params, [c.params for c in server.clients]]))
    return server, res


def timed_twice(fn):
    """Run ``fn`` cold, then again warm; returns (result of the warm run,
    cold seconds, warm seconds)."""
    t0 = time.perf_counter()
    fn()
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = fn()
    return out, cold, time.perf_counter() - t0


# ----------------------------------------------------------------- phases

def phase_device(chips: int) -> dict:
    devs = jax.devices()
    d0 = devs[0]
    print(f"devices: {devs}", flush=True)
    print(f"platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)}", flush=True)
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (platform {d0.platform!r}); "
                 "this script runs on the chip only")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} devices, "
                 f"{len(devs)} visible")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def kernel_cases(spec, clients: int, tail_clients: int, seed: int = SEED):
    """(kernel, leaf, fn, args, oracle) for each kernel at each leaf shape
    of ``spec`` with a fleet of ``clients``, and sparse_agg again with a
    fleet of ``tail_clients``."""
    params = jax.eval_shape(lambda: init_cnn_spec(jax.random.PRNGKey(0),
                                                  spec))
    key = jax.random.PRNGKey(seed)

    def rows(w):
        """(..., C) -> (C, F): the oracles' channel-major layout."""
        return w.reshape(-1, w.shape[-1]).T

    def imp_ref(wo, wn):
        n, c = wo.shape[0], wo.shape[-1]
        flat = [jnp.swapaxes(w.reshape(n, -1, c), 1, 2).reshape(n * c, -1)
                for w in (wo, wn)]
        return channel_importance_ref(*flat).reshape(n, c)

    def agg_ref(sw, sm, w):
        n, lanes = sw.shape[0], sw.shape[-1]
        return masked_weighted_sum_ref(sw.reshape(n, -1, lanes),
                                       sm.reshape(n, -1, lanes), w)

    def agg_kernel(sw, sm, w):
        lanes = sw.shape[-1]
        num, den = agg_ops.masked_weighted_sum(sw, sm, w)
        return num.reshape(-1, lanes), den.reshape(-1, lanes)

    def merge_ref(g, l, m):
        return masked_merge_ref(rows(g), rows(l), m).T.reshape(l.shape)

    cases = []
    for i, (path, leaf) in enumerate(
            jax.tree_util.tree_flatten_with_path(params)[0]):
        name = jax.tree_util.keystr(path)
        shape, c = leaf.shape, leaf.shape[-1]
        ks = jax.random.split(jax.random.fold_in(key, i), 4)
        w_old = jax.random.normal(ks[0], (clients, *shape))
        w_new = w_old + 0.1 * jax.random.normal(ks[1], w_old.shape)
        mask = (jax.random.uniform(ks[2], (clients,) + (1,) * (len(shape)
                                                           - 1) + (c,))
                > 0.5).astype(jnp.float32)
        # per-channel masks, broadcast to the leaf as the engine sends them
        full = jnp.broadcast_to(mask, w_new.shape)
        wts = jax.random.uniform(ks[3], (clients,)) + 0.5
        n = tail_clients
        cases += [
            ("importance", name,
             lambda wo, wn: imp_ops.channel_importance_batched(
                 wo, wn, channel_axis=-1), (w_old, w_new), imp_ref),
            ("sparse_agg", name, agg_kernel, (w_new, full, wts), agg_ref),
            ("sparse_agg", name, agg_kernel,
             (w_new[:n], full[:n], wts[:n]), agg_ref),
            ("masked_merge", name,
             lambda g, l, m: mm_ops.masked_merge(g, l, m, channel_axis=-1),
             (w_old[0], w_new[0], mask[0].reshape(c)), merge_ref),
        ]
    return cases


def phase_kernels(spec, clients: int, tail_clients: int) -> Failures:
    """Each kernel at each leaf shape: compiled program, custom call
    present, output against the oracle."""
    fails = Failures()
    custom = {}
    for kname, leaf, fn, args, oracle in kernel_cases(spec, clients,
                                                      tail_clients):
        compiled = jax.jit(fn).lower(*args).compile()
        has_call = "tpu_custom_call" in compiled.as_text()
        custom[kname] = custom.get(kname, True) and has_call
        rtol, atol = KERNEL_TOL[kname]
        fails.check(f"{kname} {leaf} {tuple(args[0].shape)} "
                    "|err| / (atol + rtol*|ref|)",
                    excess(compiled(*args), jax.jit(oracle)(*args),
                           rtol, atol), 1.0)
    for kname, ok in custom.items():
        print(f"  {'ok  ' if ok else 'FAIL'} {kname}: tpu_custom_call in "
              f"every compiled program: {ok}", flush=True)
        if not ok:
            fails.append(f"{kname} custom call")
    return fails


def phase_fleet(spec, clients: int, rounds: int, k: int):
    """Homogeneous fleet: per-round fused, scanned, scanned with the
    kernels; then fused vs the reference loop on a 4-client slice.  Timed
    as a user runs it, compared in both ``MODES``."""
    fails = Failures()
    params, tel, xs, ys, client_step = make_fleet(spec, clients)
    train = jax.jit(make_batched_train_fn(client_step, (xs, ys)))
    base = dict(scheme="feddd", rounds=rounds, a_server=0.6, h=5,
                seed=SEED, allocator="jax")
    steady = {}
    for tag, sel, precision in MODES:
        runs = {}
        kw = dict(base, rounds=CONTROL_ROUNDS if tag else rounds,
                  selection=sel)
        for name, cfg in [
                ("per_round", ProtocolConfig(**kw)),
                ("scanned", ProtocolConfig(rounds_per_dispatch=k, **kw)),
                ("scanned_kernel", ProtocolConfig(
                    rounds_per_dispatch=k,
                    **dict(kw, selection=dataclasses.replace(
                        sel, use_kernel=True))))]:
            def go():
                with jax.default_matmul_precision(precision):
                    return run_server(params, tel, cfg,
                                      batched_train_fn=train)
            if tag:
                srv, res = go()
            else:
                (srv, res), cold, warm = timed_twice(go)
                steady[name] = warm / rounds
                print(f"  {name}: cold {cold!r} s, warm {warm!r} s for "
                      f"{rounds} rounds", flush=True)
            runs[name] = learning_state(res, srv)
            print(f"  {tag}{name}: losses {runs[name]['loss'].tolist()}",
                  flush=True)
        compare_states(fails, f"{tag}scanned vs per_round", runs["scanned"],
                       runs["per_round"], params, bool(tag))
        compare_states(fails, f"{tag}scanned_kernel vs scanned",
                       runs["scanned_kernel"], runs["scanned"], params,
                       bool(tag))

    # fused engine vs the per-client reference loop, 4 clients x 2 rounds
    # in both modes
    n4 = 4
    tel4 = sample_system_telemetry(n4, tel.model_bytes[:n4],
                                   tel.num_samples[:n4], [1.0] * n4,
                                   seed=SEED)
    step = jax.jit(client_step)
    train4 = jax.jit(make_batched_train_fn(client_step, (xs[:n4], ys[:n4])))
    for tag, sel, precision in MODES:
        cfg4 = dict(base, rounds=CONTROL_ROUNDS, selection=sel)
        with jax.default_matmul_precision(precision):
            srv_f, res_f = run_server(params, tel4, ProtocolConfig(**cfg4),
                                      batched_train_fn=train4)
            srv_l, res_l = run_server(
                params, tel4, ProtocolConfig(batched=False, **cfg4),
                local_train_fn=lambda p, i, rng: step(p, xs[i], ys[i]))
        compare_states(fails, f"{tag}fused vs loop (4 clients, 2 rounds)",
                       learning_state(res_f, srv_f),
                       learning_state(res_l, srv_l), params, bool(tag))
    return fails, steady


def phase_ragged(specs, rounds: int):
    """The hetero-a widths through run_scheme: grouped engine vs loop."""
    fails = Failures()
    n = len(specs)
    train, _ = make_dataset("cifar10", num_train=n * SHARD, num_test=16,
                            seed=SEED)
    parts = np.arange(n * SHARD).reshape(n, SHARD)
    clients = [init_cnn_spec(jax.random.PRNGKey(100 + i), s)
               for i, s in enumerate(specs)]
    global_params = init_cnn_spec(jax.random.PRNGKey(SEED), specs[0])
    fns = [make_local_train_fn(s, train, parts, lr=LR, batch_size=BATCH)
           for s in specs]
    tel = sample_system_telemetry(n, [model_bytes(p) for p in clients],
                                  [SHARD] * n, [1.0] * n, seed=SEED)

    def ltf(params, idx, rng):
        return fns[idx](params, idx, rng)

    def run(batched):
        res = run_scheme("feddd", global_params, tel, ltf,
                         client_params=clients, rounds=rounds, a_server=0.6,
                         h=5, seed=SEED, batched=batched)
        jax.block_until_ready(jax.tree_util.tree_leaves(res.global_params))
        return res

    res_g, cold, warm = timed_twice(lambda: run(True))
    print(f"  grouped: cold {cold!r} s, warm {warm!r} s for {rounds} "
          "rounds", flush=True)
    res_l = run(False)
    compare_states(fails, "grouped vs loop", learning_state(res_g),
                   learning_state(res_l), global_params)
    return fails, {"grouped": warm / rounds}


def phase_mesh(spec, clients: int, rounds: int, chips: int):
    """ShardedRoundEngine over ``chips`` devices, dense and sparse, against
    the fused single-device engine on the same fleet; timed as a user runs
    it, compared in both ``MODES``."""
    fails = Failures()
    params, tel, xs, ys, client_step = make_fleet(spec, clients)
    base = dict(scheme="feddd", rounds=rounds, a_server=0.6, h=5,
                seed=SEED, allocator="jax")
    single = jax.jit(make_batched_train_fn(client_step, (xs, ys)))
    # the fleet's data lives on the client shards like its parameters
    mesh = make_client_mesh(chips)
    rows = jax.sharding.NamedSharding(mesh,
                                      jax.sharding.PartitionSpec("clients"))
    sharded = jax.jit(make_batched_train_fn(
        client_step, (jax.device_put(xs, rows), jax.device_put(ys, rows))))
    steady = {}
    for tag, sel, precision in MODES:
        mode = dict(base, rounds=CONTROL_ROUNDS if tag else rounds,
                    selection=sel)

        def run(train, **kw):
            with jax.default_matmul_precision(precision):
                return run_server(params, tel, ProtocolConfig(**kw, **mode),
                                  batched_train_fn=train)
        if tag:
            srv, res = run(single)
        else:
            (srv, res), cold, warm = timed_twice(lambda: run(single))
            steady["single_device"] = warm / rounds
            print(f"  single-device fused: cold {cold!r} s, warm {warm!r} s",
                  flush=True)
        ref = learning_state(res, srv)
        for collective in ("dense", "sparse"):
            def go():
                return run(sharded, mesh=chips, mesh_collective=collective)
            if tag:
                srv, res = go()
            else:
                (srv, res), cold, warm = timed_twice(go)
                steady[f"mesh_{collective}"] = warm / rounds
                print(f"  mesh {collective}: cold {cold!r} s, warm "
                      f"{warm!r} s", flush=True)
            span = {d for leaf in jax.tree_util.tree_leaves(
                res.global_params) for d in leaf.sharding.device_set}
            print(f"  {tag}mesh {collective}: global params on devices "
                  f"{sorted(d.id for d in span)}", flush=True)
            fails.check(f"{tag}{collective}: devices the global params "
                        f"span (short of {chips})",
                        float(chips - len(span)), 0.0)
            compare_states(fails, f"{tag}mesh {collective} vs single device",
                           learning_state(res, srv), ref, params, bool(tag))
    return fails, steady


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases 1-4 on one chip; 4: only the "
                         "client-sharded engine across four chips")
    args = ap.parse_args(argv)
    cache = enable_compile_cache()
    n_cached = sum(1 for _ in cache.glob("*")) if cache.is_dir() else 0
    print(f"compile cache: {cache} ({n_cached} entries at start)",
          flush=True)
    t_start = time.perf_counter()

    t0 = time.perf_counter()
    device = phase_device(args.chips)
    ok = report("device", time.perf_counter() - t0, None, [])
    if args.chips == 1:
        t0 = time.perf_counter()
        fails = phase_kernels(VGG, CLIENTS, TAIL_CLIENTS)
        ok &= report("kernels", time.perf_counter() - t0, None, fails)
        t0 = time.perf_counter()
        fails, steady = phase_fleet(VGG, CLIENTS, ROUNDS, K)
        ok &= report("fleet", time.perf_counter() - t0, steady, fails)
        t0 = time.perf_counter()
        fails, steady = phase_ragged(HETERO_A_SPECS, 2)
        ok &= report("ragged", time.perf_counter() - t0, steady, fails)
    else:
        t0 = time.perf_counter()
        fails, steady = phase_mesh(VGG, CLIENTS, 3, args.chips)
        ok &= report("mesh", time.perf_counter() - t0, steady, fails)
    print(f"total {time.perf_counter() - t_start!r} s", flush=True)
    if not ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
