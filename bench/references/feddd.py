"""Plain FedDD server and local training (paper arXiv 2308.16835), for
any client model whose plain forward pass a family's reference hands in.

Written from the paper's equations and imports nothing of the program:
it rebuilds the weights, data and telemetry from the seed through the
cell's family (``bench/families``) and ``bench/fleet.py``, and follows
the first rounds of a run:

* local training: ``epochs`` passes of minibatch SGD per client, in the
  stored sample order or, for per-client trainers, in the order of
  ``jax.random.permutation(fold_in(fold_in(round_key, client), epoch),
  samples)`` (the round keys split from ``PRNGKey(seed)``, one per round);
* Eq. (20)/(21) importance: per output channel (a leaf's last axis), the
  L2 norm over the other axes of ``|dW * W_new / W_old|`` (``|W_old| <
  1e-8`` replaced by ``+-1e-8``), divided by the channel's coverage rate
  in a ragged fleet;
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

Placement: the clients of each sub-model are held in ``setup["shards"]``
contiguous parts, part ``p`` on device ``p`` (one part on the default
device for a one-chip cell), each trained in blocks of ``block`` clients;
each part's Eq. (4) (num, den) partials are summed on the default device.
So a fleet larger than one chip holds is spread over the cell's chips.

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

from bench import compare, families, fleet

_EPS_W = 1e-8       # importance: guard of the division by W_old
_EPS_DEN = 1e-12    # Eq. (4): positions no client uploaded


def xent(logits, y):
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(logz - jnp.take_along_axis(logits, y[..., None],
                                               -1)[..., 0])


@functools.lru_cache(maxsize=None)
def _train_block(forward, model, lr, precision):
    """vmapped local SGD over a block of same-width clients;
    ``forward(params, model, x, precision) -> logits``."""
    def client(p, x, y, order):
        def one(p, idx):
            loss, g = jax.value_and_grad(
                lambda q: xent(forward(q, model, x[idx], precision),
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

def _is_pair(x):
    return isinstance(x, tuple)


def run(setup: Dict, calls: Sequence[int], forward, *,
        dtype: str = "float32", precision: str = "default",
        block: int = 32) -> Dict:
    """Follow the first rounds of a run from the seed, made as
    ``FedDDServer.run`` calls of ``calls`` rounds each (every call counts
    its rounds from 1, so its round ``t`` with ``t % h == 0`` is the full
    broadcast; the state and the PRNG stream run on across calls).

    ``setup`` (built by ``bench/run.py`` from the configuration): seed,
    family, global_model, client_models (one description per client), xs,
    ys (host arrays, ``(clients, samples, ...)``), telemetry, lr, batch,
    epochs, a_server, d_max, delta, h, order ("stored" or "permuted"),
    shards.  ``forward(params, model, x, precision) -> logits`` is the
    family's plain forward pass.

    Returns the readings the comparison takes: ``first_update`` (leaf ->
    the global model's change in the first call, float32 host array),
    ``rates`` (R, N), ``global_change`` and ``clients_change`` (leaf -> L2
    norm of the change from the initial weights after the last call).
    """
    dt = jnp.dtype(dtype)
    prec = (jax.lax.Precision.HIGHEST if precision == "highest"
            else jax.lax.Precision.DEFAULT)
    family = families.load(setup["family"])
    seed = setup["seed"]
    n = len(setup["client_models"])
    devices = jax.devices()[:setup["shards"]]
    home = devices[0]
    # width groups, in order of first member
    groups: List[Dict] = []
    for i, model in enumerate(setup["client_models"]):
        for g in groups:
            if g["model"] == model:
                g["idx"].append(i)
                break
        else:
            groups.append({"model": model, "idx": [i]})
    g0, subs = family.make_weights(seed, setup["global_model"],
                                   [g["model"] for g in groups])
    gshapes = jax.tree_util.tree_map(lambda a: a.shape, g0)
    cast = lambda t: jax.tree_util.tree_map(lambda a: a.astype(dt), t)
    gparams = cast(g0)
    # coverage rate of every full-width channel
    kind_widths = [{k: a.shape[-1] for k, a in compare.leaves(s).items()}
                   for s in subs]
    widths = [kind_widths[gi] for i in range(n) for gi, g in
              enumerate(groups) if i in g["idx"]]
    cr = {k: np.mean([np.arange(a.shape[-1]) < w.get(k, 0)
                      for w in widths], axis=0)
          for k, a in compare.leaves(g0).items()}
    weights = np.asarray(setup["telemetry"]["num_samples"], np.float32)
    xs = setup["xs"]
    xdt = dt if np.issubdtype(xs.dtype, np.floating) else xs.dtype
    for gi, g in enumerate(groups):
        idx = np.asarray(g["idx"])
        g["n"] = len(idx)
        cov = jax.tree_util.tree_map_with_path(
            lambda path, a: jnp.asarray(cr[jax.tree_util.keystr(
                path, simple=True, separator=".")][:a.shape[-1]]),
            subs[gi])
        per = -(-len(idx) // len(devices))
        g["parts"] = []
        for p, dev in enumerate(devices):
            rows = slice(p * per, min((p + 1) * per, len(idx)))
            pidx = idx[rows]
            if not len(pidx):
                continue
            put = functools.partial(jax.device_put, device=dev)
            g["parts"].append({
                "rows": rows, "idx": pidx, "n": len(pidx), "device": dev,
                "params": put(jax.tree_util.tree_map(
                    lambda a: jnp.broadcast_to(a.astype(dt),
                                               (len(pidx), *a.shape)),
                    subs[gi])),
                "x": put(jnp.asarray(xs[pidx], xdt)),
                "y": put(jnp.asarray(setup["ys"][pidx])),
                "w": put(jnp.asarray(weights[pidx])),
                "cov": put(cov)})
        g["train"] = _train_block(forward, g["model"], setup["lr"], prec)
    samples, batch = xs.shape[1], setup["batch"]
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
        loss_parts = []
        num = den = None
        for g in groups:
            if setup["order"] == "stored":
                order = np.broadcast_to(stored, (g["n"], *stored.shape))
            else:
                order = np.stack([np.concatenate([
                    np.asarray(jax.random.permutation(
                        jax.random.fold_in(jax.random.fold_in(rk, int(i)),
                                           ep), samples))[:steps * batch]
                    .reshape(steps, batch)
                    for ep in range(setup["epochs"])]) for i in g["idx"]])
            for part in g["parts"]:
                porder = jnp.asarray(order[part["rows"]])
                new_blocks = []
                for s in range(0, part["n"], block):
                    sl = slice(s, s + block)
                    p, l = g["train"](
                        jax.tree_util.tree_map(lambda a: a[sl],
                                               part["params"]),
                        part["x"][sl], part["y"][sl], porder[sl])
                    new_blocks.append(p)
                    loss_parts.append((part["idx"][sl], l))
                new = jax.tree_util.tree_map(
                    lambda *a: jnp.concatenate(a), *new_blocks)
                part["masks"] = _masks(
                    part["params"], new,
                    jnp.asarray(rates[part["idx"]], jnp.float32),
                    part["cov"])
                part["new"] = new
                pair = jax.device_put(
                    _group_partials(new, part["masks"], part["w"]), home)
                pnum, pden = (jax.tree_util.tree_map(
                    lambda nd, shp: _pad(nd[j], shp), pair, gshapes,
                    is_leaf=_is_pair) for j in (0, 1))
                if num is None:
                    num, den = pnum, pden
                else:
                    num = jax.tree_util.tree_map(jnp.add, num, pnum)
                    den = jax.tree_util.tree_map(jnp.add, den, pden)
        for pidx, l in loss_parts:
            losses[pidx] = np.asarray(l, np.float64)
        gparams = jax.tree_util.tree_map(
            lambda a, d, prev: jnp.where(d > _EPS_DEN,
                                         a / jnp.maximum(d, _EPS_DEN),
                                         prev).astype(dt),
            num, den, gparams)
        for g in groups:
            for part in g["parts"]:
                part["params"] = _client_update(
                    jax.device_put(gparams, part["device"]),
                    part.pop("new"), part.pop("masks"), t % setup["h"] == 0)
        rates = allocate(setup["telemetry"], np.maximum(losses, 1e-6),
                         a_server=setup["a_server"], d_max=setup["d_max"],
                         delta=setup["delta"],
                         global_bytes=family.param_bytes(
                             setup["global_model"]))
        out["rates"].append(rates.copy())
        if r == calls[0]:
            g_now, g_first = compare.leaves(gparams), compare.leaves(g0)
            out["first_update"] = {
                k: np.asarray(a, np.float32)
                - np.asarray(g_first[k], np.float32)
                for k, a in g_now.items()}
    out["rates"] = np.stack(out["rates"])
    out["global_change"] = compare.change_norms(gparams, g0)
    out["clients_change"] = clients_change_norms(
        [part["params"] for g in groups for part in g["parts"]], g0)
    return out


def clients_change_norms(stacks, origin) -> Dict[str, float]:
    """Leaf path -> L2 norm over every client of its change from the
    initial global (sliced to the client's widths).  ``stacks``: stacked
    params, each of clients of one width on one device."""
    origin = compare.leaves(origin)
    total: Dict[str, float] = {}
    for params in stacks:
        for k, a in compare.leaves(params).items():
            o = jax.device_put(
                origin[k][tuple(slice(0, s) for s in a.shape[1:])],
                a.sharding)
            total[k] = total.get(k, 0.0) + float(
                jnp.sum(jnp.square(a.astype(jnp.float32)
                                   - o.astype(jnp.float32)[None])))
    return {k: math.sqrt(v) for k, v in total.items()}
