"""Plain FedDD reference for VGG fleets (paper arXiv 2308.16835).

Written from the paper's equations and imports nothing of the program:
it rebuilds the weights, data and telemetry from the seed through
``bench/fleet.py`` and follows the first rounds of a run:

* local training: ``epochs`` passes of minibatch SGD per client, in the
  stored sample order or, for per-client trainers, in the order of
  ``jax.random.permutation(fold_in(fold_in(round_key, client), epoch),
  samples)`` (the round keys split from ``PRNGKey(seed)``, one per round);
* Eq. (20)/(21) importance: per output channel, the L2 norm over the
  other axes of ``|dW * W_new / W_old|`` (``|W_old| < 1e-8`` replaced by
  ``+-1e-8``), divided by the channel's coverage rate in a ragged fleet;
* Algorithm 2 masks: each client keeps, per leaf, the ``ceil(C (1 - D))``
  channels of highest importance (float32 rates, as the configuration
  states; ties to the lower channel index);
* Eq. (4): the masked weighted mean over the clients' uploads, on the
  full-width canvas (sub-models zero-padded), keeping the previous global
  where no client uploaded;
* Eq. (5) on rounds ``t % h != 0``: uploaded channels take the global,
  the rest keep the local update; Eq. (6) on rounds ``t % h == 0``: every
  client takes the global (sliced to its widths);
* Eq. (9)-(11), the dropout-rate LP: ``min t + delta * sum re_n D_n``
  subject to ``0 <= D_n <= D_max``, ``sum U_n (1 - D_n) = A_server sum
  U_n`` and ``t_cmp_n + U_n (1 - D_n)(1/r_u + 1/r_d) <= t``, solved
  exactly in float64: the objective is convex and piecewise linear in
  ``t``, so its minimum lies at the least feasible ``t`` or at a
  breakpoint, each of which is a fractional knapsack.

``dtype="float32"`` with ``precision="default"`` is the reference, at
the precision the configuration states: float32 parameters, activations
and updates, with every convolution and matmul, in the forward and in the
backward alike, at the default precision, which on the TPU rounds both
operands to bfloat16 and accumulates in float32 (one MXU pass).
``dtype="bfloat16"`` computes everything on the device in bfloat16 (the
control, the precision below the configuration's float32).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from bench import compare, fleet

_EPS_W = 1e-8       # importance: guard of the division by W_old
_EPS_DEN = 1e-12    # Eq. (4): positions no client uploaded


# ------------------------------------------------------------------ model

def forward(params, layers, x, precision):
    li, n_fc, seen = 0, sum(l[0] == "fc" for l in layers), 0
    for layer in layers:
        if layer[0] == "conv":
            p = params[f"conv{li}"]
            x = jax.lax.conv_general_dilated(
                x, p["w"], (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=precision)
            x = jax.nn.relu(x + p["b"])
            li += 1
        elif layer[0] == "pool":
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        else:
            x = x.reshape(x.shape[0], -1)
            p = params[f"fc{li}"]
            x = jnp.dot(x, p["w"], precision=precision) + p["b"]
            seen += 1
            if seen < n_fc:
                x = jax.nn.relu(x)
            li += 1
    return x


def xent(logits, y):
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(logz - jnp.take_along_axis(logits, y[:, None], -1)[:, 0])


@functools.lru_cache(maxsize=None)
def _train_block(layers, lr, precision):
    """vmapped local SGD over a block of same-width clients."""
    def client(p, x, y, order):
        def one(p, idx):
            loss, g = jax.value_and_grad(
                lambda q: xent(forward(q, layers, x[idx], precision),
                               y[idx]))(p)
            return jax.tree_util.tree_map(
                lambda w, d: (w - lr * d).astype(w.dtype), p, g), loss
        p, losses = jax.lax.scan(one, p, order)
        return p, jnp.mean(losses)
    return jax.jit(jax.vmap(client))


# ---------------------------------------------------------- FedDD server

@jax.jit
def _masks(old, new, rates, cov):
    """Per-leaf (n, C) keep masks of a stacked group."""
    def leaf(wo, wn, cr):
        safe = jnp.where(jnp.abs(wo) < _EPS_W,
                         jnp.where(wo < 0, -_EPS_W, _EPS_W), wo)
        imp = jnp.abs((wn - wo) * wn / safe)
        axes = tuple(range(1, imp.ndim - 1))
        score = jnp.sqrt(jnp.sum(imp * imp, axis=axes)) if axes else imp
        score = score / jnp.maximum(cr, _EPS_W).astype(score.dtype)
        c = wn.shape[-1]
        keep = jnp.clip(jnp.ceil(jnp.float32(c) * (1.0 - rates)), 0, c)
        order = jnp.argsort(-score, axis=-1, stable=True)
        ranks = jnp.argsort(order, axis=-1, stable=True)
        return (ranks < keep[:, None]).astype(wn.dtype)
    return jax.tree_util.tree_map(leaf, old, new, cov)


@jax.jit
def _group_partials(new, masks, w):
    """Eq. (4) numerator and denominator of one group, at its widths."""
    def leaf(wn, m):
        mb = m.reshape(m.shape[0], *([1] * (wn.ndim - 2)), m.shape[-1])
        wt = w.reshape(-1, *([1] * (wn.ndim - 1))).astype(wn.dtype)
        return (jnp.sum(wn * mb * wt, axis=0),
                jnp.sum(jnp.broadcast_to(mb, wn.shape) * wt, axis=0))
    return jax.tree_util.tree_map(leaf, new, masks)


def _pad(x, shape):
    return jnp.pad(x, [(0, s - d) for s, d in zip(shape, x.shape)])


@functools.partial(jax.jit, static_argnums=3)
def _client_update(g, new, masks, full):
    def leaf(gl, wn, m):
        gl = gl[tuple(slice(0, s) for s in wn.shape[1:])][None]
        if full:
            return jnp.broadcast_to(gl, wn.shape).astype(wn.dtype)
        mb = m.reshape(m.shape[0], *([1] * (wn.ndim - 2)), m.shape[-1])
        return (gl * mb + wn * (1 - mb)).astype(wn.dtype)
    return jax.tree_util.tree_map(leaf, g, new, masks)


def allocate(tel: Dict, losses, *, a_server, d_max, delta, global_bytes):
    """The Eq. (9)-(11) LP, exactly, in float64 (see the module doc)."""
    u = np.asarray(tel["model_bytes"], np.float64)
    k = u * (1.0 / tel["uplink_rate"] + 1.0 / tel["downlink_rate"])
    tc = np.asarray(tel["compute_latency"], np.float64)
    m = np.asarray(tel["num_samples"], np.float64)
    costs = delta * (m / m.sum()) * tel["label_coverage"] \
        * (u / global_bytes) * np.asarray(losses, np.float64)
    budget = (1.0 - a_server) * u.sum()
    if u.sum() * d_max < budget:
        return np.full(len(u), min(1.0 - a_server, d_max))

    def lower(t):
        return np.clip(1.0 - (t - tc) / k, 0.0, None)

    def mass(t):
        return float(np.dot(u, lower(t)))

    t_lo = float(np.max(tc + k * (1.0 - d_max)))
    t_hi = float(np.max(tc + k))
    bps = np.sort(np.unique(np.concatenate([[t_lo, t_hi], tc + k])))
    bps = bps[(bps >= t_lo) & (bps <= t_hi)]
    if mass(t_lo) <= budget:
        t_f = t_lo
    else:       # mass is linear between breakpoints: solve the segment
        j = int(np.argmax([mass(b) <= budget for b in bps]))
        a, b = bps[j - 1], bps[j]
        ma, mb = mass(a), mass(b)
        t_f = a + (ma - budget) * (b - a) / (ma - mb)

    def solve(t):
        d = np.minimum(lower(t), d_max)
        rest = budget - float(np.dot(u, d))
        for i in np.argsort(costs / u, kind="stable"):
            if rest <= 0:
                break
            take = min((d_max - d[i]) * u[i], rest)
            d[i] += take / u[i]
            rest -= take
        return d, t + float(np.dot(costs, d))

    best = min((solve(t) for t in [t_f, *bps[bps > t_f]]),
               key=lambda s: s[1])
    return np.clip(best[0], 0.0, d_max)


# -------------------------------------------------------------- the run

def run(setup: Dict, calls: Sequence[int], *, dtype: str = "float32",
        precision: str = "default", block: int = 32) -> Dict:
    """Follow the first rounds of a run from the seed, made as
    ``FedDDServer.run`` calls of ``calls`` rounds each (every call counts
    its rounds from 1, so its round ``t`` with ``t % h == 0`` is the full
    broadcast; the state and the PRNG stream run on across calls).

    ``setup`` (built by ``bench/run.py`` from the configuration): seed,
    global_layers, client_layers (one layer list per client), xs, ys
    (host arrays, ``(clients, samples, ...)``), telemetry,
    lr, batch, epochs, a_server, d_max, delta, h, order ("stored" or
    "permuted").

    Returns the readings the comparison takes: ``first_update`` (leaf ->
    the global model's change in the first call, float32 host array),
    ``rates`` (R, N), ``global_change`` and ``clients_change`` (leaf -> L2
    norm of the change from the initial weights after the last call).
    """
    dt = jnp.dtype(dtype)
    prec = (jax.lax.Precision.HIGHEST if precision == "highest"
            else jax.lax.Precision.DEFAULT)
    seed = setup["seed"]
    n = len(setup["client_layers"])
    # width groups, in order of first member
    groups: List[Dict] = []
    for i, layers in enumerate(setup["client_layers"]):
        for g in groups:
            if g["layers"] == layers:
                g["idx"].append(i)
                break
        else:
            groups.append({"layers": layers, "idx": [i]})
    g0, subs = fleet.make_weights(seed, setup["global_layers"],
                                  [g["layers"] for g in groups])
    gshapes = fleet.leaf_shapes(setup["global_layers"])
    cast = lambda t: jax.tree_util.tree_map(lambda a: a.astype(dt), t)
    gparams = cast(g0)
    # coverage rate of every full-width channel
    widths = [{(name, k): shp[k][-1] for name, shp in
               fleet.leaf_shapes(l).items() for k in shp}
              for l in setup["client_layers"]]
    cr = {(name, k): np.mean([np.arange(shp[-1]) < w.get((name, k), 0)
                              for w in widths], axis=0)
          for name, leaves in gshapes.items() for k, shp in leaves.items()}
    weights = np.asarray(setup["telemetry"]["num_samples"], np.float32)
    for g in groups:
        idx = np.asarray(g["idx"])
        g["n"] = len(idx)
        g["params"] = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a.astype(dt), (len(idx), *a.shape)),
            subs[groups.index(g)])
        g["x"] = jnp.asarray(setup["xs"][idx], dt)
        g["y"] = jnp.asarray(setup["ys"][idx])
        g["w"] = jnp.asarray(weights[idx])
        g["cov"] = {name: {k: jnp.asarray(cr[(name, k)][:shp[k][-1]])
                           for k in shp}
                    for name, shp in fleet.leaf_shapes(g["layers"]).items()}
        g["train"] = _train_block(g["layers"], setup["lr"], prec)
    samples, batch = setup["xs"].shape[1], setup["batch"]
    steps = samples // batch
    stored = np.tile(np.arange(steps * batch).reshape(steps, batch),
                     (setup["epochs"], 1))
    rates = np.zeros(n)
    rng = jax.random.PRNGKey(fleet.seed32(seed))
    out = {"rates": []}
    schedule = [t for c in calls for t in range(1, c + 1)]
    for r, t in enumerate(schedule, start=1):
        rng, rk = jax.random.split(rng)
        losses = np.zeros(n)
        num = den = None
        for g in groups:
            idx = np.asarray(g["idx"])
            if setup["order"] == "stored":
                order = np.broadcast_to(stored, (g["n"], *stored.shape))
            else:
                order = np.stack([np.concatenate([
                    np.asarray(jax.random.permutation(
                        jax.random.fold_in(jax.random.fold_in(rk, int(i)),
                                           ep), samples))[:steps * batch]
                    .reshape(steps, batch)
                    for ep in range(setup["epochs"])]) for i in idx])
            order = jnp.asarray(order)
            new_parts, loss_parts = [], []
            for s in range(0, g["n"], block):
                sl = slice(s, s + block)
                p, l = g["train"](
                    jax.tree_util.tree_map(lambda a: a[sl], g["params"]),
                    g["x"][sl], g["y"][sl], order[sl])
                new_parts.append(p)
                loss_parts.append(np.asarray(l, np.float64))
            new = jax.tree_util.tree_map(
                lambda *a: jnp.concatenate(a), *new_parts)
            losses[idx] = np.concatenate(loss_parts)
            g["masks"] = _masks(g["params"], new,
                                jnp.asarray(rates[idx], jnp.float32),
                                g["cov"])
            g["new"] = new
            part = _group_partials(new, g["masks"], g["w"])
            padded = {name: {k: (_pad(part[name][k][0], gshapes[name][k]),
                                 _pad(part[name][k][1], gshapes[name][k]))
                             for k in part[name]} for name in part}
            if num is None:
                num = {nm: {k: v[0] for k, v in lv.items()}
                       for nm, lv in padded.items()}
                den = {nm: {k: v[1] for k, v in lv.items()}
                       for nm, lv in padded.items()}
            else:
                for nm, lv in padded.items():
                    for k, v in lv.items():
                        num[nm][k] = num[nm][k] + v[0]
                        den[nm][k] = den[nm][k] + v[1]
        gparams = jax.tree_util.tree_map(
            lambda a, d, prev: jnp.where(d > _EPS_DEN,
                                         a / jnp.maximum(d, _EPS_DEN),
                                         prev).astype(dt),
            num, den, gparams)
        for g in groups:
            g["params"] = _client_update(gparams, g.pop("new"),
                                         g.pop("masks"), t % setup["h"] == 0)
        rates = allocate(setup["telemetry"], np.maximum(losses, 1e-6),
                         a_server=setup["a_server"], d_max=setup["d_max"],
                         delta=setup["delta"],
                         global_bytes=fleet.param_bytes(
                             setup["global_layers"]))
        out["rates"].append(rates.copy())
        if r == calls[0]:
            out["first_update"] = {
                f"{nm}.{k}": np.asarray(gparams[nm][k], np.float32)
                - np.asarray(g0[nm][k], np.float32)
                for nm in gparams for k in gparams[nm]}
    out["rates"] = np.stack(out["rates"])
    out["global_change"] = compare.change_norms(gparams, g0)
    out["clients_change"] = clients_change_norms(
        [(g["params"], g["n"]) for g in groups], g0)
    return out


def clients_change_norms(stacks, origin) -> Dict[str, float]:
    """Leaf path -> L2 norm over every client of its change from the
    initial global (sliced to the client's widths).  ``stacks``: (stacked
    params, clients) pairs, each stack of one width."""
    total: Dict[str, float] = {}
    for params, _ in stacks:
        for nm in params:
            for k, a in params[nm].items():
                o = origin[nm][k][tuple(slice(0, s) for s in a.shape[1:])]
                total[f"{nm}.{k}"] = total.get(f"{nm}.{k}", 0.0) + float(
                    jnp.sum(jnp.square(a.astype(jnp.float32)
                                       - o.astype(jnp.float32)[None])))
    return {k: math.sqrt(v) for k, v in total.items()}
