"""Batched FedDD round engine — the homogeneous hot path, fully on device.

``FedDDServer.run`` executes Algorithm 1 as a Python loop over clients:
per-client ``build_masks`` dispatches, per-leaf ``float(...)`` host syncs in
``mask_density``, list-based padding and aggregation.  At simulation scale
(hundreds of clients) dispatch overhead — not compute — dominates.

This module stacks client parameter pytrees along a leading client axis and
rewrites the round's server side as ONE ``jax.jit``-compiled step:

    importance scoring   — client axis folded into the channel axis, one
                           pass per leaf (Pallas kernel when use_kernel)
    mask building        — full-width ``lax.top_k`` ranks + a dynamic
                           ``rank < keep`` compare, vmapped over clients
    masked aggregation   — Eq. (4) over the already-stacked leaves
                           (Pallas sparse_agg kernel when use_kernel)
    sparse client update — Eq. (5)/(6) broadcast over the client axis

Per-round device->host traffic collapses to one transfer of a small
telemetry struct (per-client upload densities, plus losses when local
training is batched too) instead of O(clients x leaves) ``float()`` calls.

Results are bit-identical to the per-client loop for a fixed seed
(tests/test_round_engine.py asserts this), so ``protocol.py`` routes every
homogeneous FedDD run through this engine and keeps the loop only for
heterogeneous (ragged-width) client models.

The engine also serves the fedavg/fedcs/oort baselines (``dense_masks``:
all-ones masks, no scoring) and the event-driven simulator
(``repro.sim.runner``): non-participation, deadline-dropped stragglers, and
staleness-decayed async merges are all expressed as per-client aggregation
weights — weight 0 excludes a client from the stacked Eq. (4) reduction.

Multi-round fusion (``BatchedRoundEngine.run``): once per-round compute is
one fused step, the round LOOP itself is the remaining overhead — every
round pays a Python dispatch, an allocator call, and a (losses, densities)
device->host transfer before the next step can launch.  With the jit-able
allocator (``allocation.solve_dropout_rates_jax``) the whole train loop —
allocate -> select -> aggregate -> update -> re-allocate — lifts into a
``lax.scan`` over rounds: K rounds run as ONE device dispatch carrying
(params, losses, dropout rates, PRNG key, Eq. (12) clock) and the only
host traffic is one transfer of the stacked :class:`ScanTrace` telemetry
at the end.  ``protocol.py`` routes this via
``ProtocolConfig.rounds_per_dispatch`` and splices the trace back into the
per-round ``RoundRecord`` stream.  Equivalence contract
(tests/test_round_engine.py): the learning state — params, masks, losses,
participation — is bit-identical to K sequential engine steps, and the
Eq. (9)-(11) dropout rates match to the last float32 bit the
``optimization_barrier``-fenced allocator can pin (identical for the test
fixtures; within a few ulps in the worst case, because XLA compiles the
golden-section search per program and its final bit is context
sensitive — see ``allocation.solve_dropout_rates_jax``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.comm import codecs as wire_codecs
from repro.comm import quantize as wire_quant
from repro.comm.payload import CommConfig, WireSpec, analytic_wire_bytes
from repro.core import (aggregation, allocation, baselines, selection,
                        sparse_collective)
from repro.obs import NULL_RECORDER


class RoundOutputs(NamedTuple):
    """Device-side results of one batched round step."""

    client_params: object      # pytree, leaves (N, *leaf): W_n^{t+1}
    global_params: object      # pytree: W^t
    densities: jax.Array       # (N,) fraction of elements uploaded
    wire_overhead: object = None   # (N,) int32 measured mask/scale bytes
                                   # (repro.comm), or None with the default
                                   # CommConfig (dense codec, no overhead)
    collective_overflow: object = None  # () f32 channels that missed the
                                        # compacted cross-device buffer
                                        # (ShardedRoundEngine, sparse
                                        # collective only; 0 certifies the
                                        # compaction was lossless)


class GroupBatch(NamedTuple):
    """One shape group's device-side inputs to a grouped round step.

    Everything here is traced (a pytree): group MEMBERSHIP changes (async
    buffers, different fleets of the same shape census) re-use the compiled
    step; only the shape census itself keys the jit cache.
    """

    indices: jax.Array         # (n_g,) int32: canvas rows / RNG-fold ids
    stacked_old: object        # pytree, leaves (n_g, *local): W_n^t
    stacked_new: object        # pytree, leaves (n_g, *local): What_n^t
    coverage: object           # CR(k) pytree of (C_local,) leaves, or None
    dropout: jax.Array         # (n_g,) float32 D_n^t


class GroupedRoundOutputs(NamedTuple):
    """Device-side results of one grouped round step."""

    group_client_params: Tuple # per group: pytree, leaves (n_g, *local)
    global_params: object      # full-width pytree: W^t
    densities: jax.Array       # (N,) canvas of upload densities
    wire_overhead: object = None   # (N,) int32 canvas of measured mask /
                                   # scale bytes, or None (default comm)


class ScanTelemetry(NamedTuple):
    """Static per-run client telemetry staged on device for the scanned
    multi-round path: the Eq. (9)-(11) allocator inputs plus the Eq. (12)
    clock coefficients.  ``train_loss`` is deliberately absent — it is
    round-dynamic and lives in the :class:`ScanState` carry.
    """

    model_bytes: jax.Array     # (N,) f32 U_n
    uplink_rate: jax.Array     # (N,) f32 r_n^u
    downlink_rate: jax.Array   # (N,) f32 r_n^d
    compute_latency: jax.Array # (N,) f32 t_n^cmp
    num_samples: jax.Array     # (N,) f32 m_n
    label_coverage: jax.Array  # (N,) f32 Eq. (13) coverage term

    @classmethod
    def from_host(cls, tel) -> "ScanTelemetry":
        """Stage a :class:`repro.core.allocation.ClientTelemetry` (minus
        the dynamic ``train_loss``) as float32 device arrays."""
        return cls(*(jnp.asarray(getattr(tel, f), jnp.float32)
                     for f in cls._fields))


class ScanState(NamedTuple):
    """The ``lax.scan`` carry of the multi-round fused path — everything
    round t hands round t+1, entirely on device."""

    client_params: object      # stacked pytree, leaves (N, *leaf): W_n^t
    global_params: object      # pytree: W^{t-1}
    losses: jax.Array          # (N,) f32 server-side loss view
    dropout: jax.Array         # (N,) f32 D_t (rates the NEXT uploads use)
    rng: jax.Array             # protocol PRNG key (split once per round)
    sim_time: jax.Array        # () f32 cumulative Eq. (12) clock (device
                               # axis; chunk-relative — see ScanTrace)


class ScanTrace(NamedTuple):
    """Per-round telemetry stacked over the scanned chunk — the chunk's ONE
    device->host transfer.  ``round_time`` / ``sim_time`` are the float32
    DEVICE rendering of the Eq. (12) clock (``sim_time`` cumulative from
    the chunk start); the protocol driver recomputes the authoritative
    float64 clock host-side from ``next_dropout`` + ``participants`` so
    spliced ``RoundRecord`` streams stay bit-identical to sequential
    rounds.
    """

    losses: jax.Array          # (K, N) f32 post-round losses
    densities: jax.Array       # (K, N) f32 upload densities
    next_dropout: jax.Array    # (K, N) f32 D_{t+1} (the Eq. (9)-(11) solve)
    participants: jax.Array    # (K, N) bool round participation
    round_time: jax.Array      # (K,) f32 Eq. (12) round duration (device)
    sim_time: jax.Array        # (K,) f32 cumulative device clock
    wire_overhead: object = None   # (K, N) int32 measured mask/scale bytes
                                   # (repro.comm), or None (default comm) —
                                   # integer arithmetic, so the scanned and
                                   # per-round renderings agree exactly


@jax.jit
def _stack_compiled(trees: List) -> object:
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *trees)


@functools.partial(jax.jit, static_argnums=1)
def _unstack_compiled(stacked, n: int) -> Tuple:
    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    rows = [[lax.index_in_dim(l, i, keepdims=False) for i in range(n)]
            for l in leaves]
    return tuple(treedef.unflatten([r[i] for r in rows]) for i in range(n))


def stack_pytrees(trees: Sequence) -> object:
    """[pytree] x N (identical structure/shapes) -> pytree of (N, *leaf).

    ONE compiled dispatch per call (jit keys on the tree, the leaf avals
    and N): eager stacking costs an ``expand_dims`` per client and leaf
    plus a concatenate per leaf, each a host-side dispatch."""
    return _stack_compiled(list(trees))


def unstack_pytree(stacked, n: int) -> List:
    """Inverse of :func:`stack_pytrees`: the first ``n`` rows of every
    leaf as ``n`` per-client pytrees, in ONE compiled dispatch (no host
    sync; ``n`` is static, so each (tree, avals, n) compiles once).  The
    input is not donated: callers keep the stacked state."""
    return list(_unstack_compiled(stacked, n))


def _adopt_global(new_global, stacked):
    """Eq. (6): every client adopts the fresh global model (the un-stacked
    global broadcasts against the (N, ...) stacked leaves)."""
    return jax.tree_util.tree_map(
        lambda g, l: jnp.broadcast_to(g, l.shape).astype(l.dtype),
        new_global, stacked)


def _dense_masks(stacked, n: int):
    """All-ones channel masks + unit densities (full-model uploads)."""
    masks = jax.tree_util.tree_map(
        lambda l: jnp.ones((n,) + (1,) * (l.ndim - 1), l.dtype), stacked)
    return masks, jnp.ones((n,), jnp.float32)


def _wire_overhead(masks, stacked_new, comm: CommConfig, channel_axis: int,
                   dense_masks: bool):
    """(N,) int32 measured mask/scale bytes, or None for the default comm.

    Sparse (feddd) masks encode their actual kept sets; dense all-ones
    masks charge the closed-form full-upload constant at true channel
    widths (their in-trace representation collapses the channel dim —
    see ``wire_codecs.full_upload_overhead_bytes``).
    """
    if comm.is_default:
        return None
    n = jax.tree_util.tree_leaves(stacked_new)[0].shape[0]
    if dense_masks:
        const = wire_codecs.full_upload_overhead_bytes(
            WireSpec.from_stacked(stacked_new, channel_axis), comm)
        return jnp.full((n,), const, jnp.int32)
    return wire_codecs.mask_overhead_bytes_stacked(masks, stacked_new,
                                                   comm)


# The whole server side of Algorithm 1 (steps 2-4 + 6-7) in one trace.
# Module-level jit keyed on the (hashable, frozen) SelectionConfig so the
# compile cache is shared across engine instances and server runs.
@functools.partial(jax.jit,
                   static_argnames=("sel_cfg", "full_round", "dense_masks",
                                    "comm", "robust"))
def _round_step(stacked_old, stacked_new, global_params, dropout_rates,
                weights, rng, stacked_upload=None, delivered=None, *,
                sel_cfg: selection.SelectionConfig,
                full_round: bool, dense_masks: bool = False,
                comm: CommConfig = CommConfig(),
                robust: str = "mean") -> RoundOutputs:
    # jax.named_scope blocks are compile-time metadata (operator name
    # prefixes in the HLO / profiler traces — repro.obs vocabulary); they
    # are UNCONDITIONAL, so the compiled program never depends on whether
    # observability is enabled.
    with jax.named_scope("feddd_encode_masks"):
        if dense_masks:
            # Baseline rounds (fedavg/fedcs/oort): participants upload
            # FULL models, so masks are all-ones and no importance
            # scoring runs.  Non-participation is a 0 in ``weights`` — a
            # zero-weight client contributes nothing to either Eq. (4)
            # sum, exactly like being left out of the aggregation list.
            n = jax.tree_util.tree_leaves(stacked_new)[0].shape[0]
            masks, density = _dense_masks(stacked_new, n)
        else:
            masks, density = selection.build_masks_batched(
                stacked_old, stacked_new, dropout_rates, config=sel_cfg,
                rng=rng)
    # Wire format (repro.comm): the server aggregates what it DECODED —
    # with qbits < 32 that is the quantize->dequantize rendering of the
    # uploads (the clients' own Eq. (5) updates keep local full precision,
    # so only the aggregation input changes).  Static branch: the default
    # comm config traces the exact pre-comm graph.  Dense (all-ones)
    # masks carry a collapsed channel dim, so their overhead is the
    # closed-form full-upload constant at TRUE widths, not an encoding of
    # the collapsed shape.
    # Fault injection (repro.sim.faults): ``stacked_upload`` is what the
    # server DECODED off the wire — corrupted rows differ from the
    # client's own ``stacked_new``, which stays clean for Eq. (5);
    # ``delivered`` truncates deadline-cut uploads to the per-leaf prefix
    # of mask channels whose bytes landed (partial aggregation).  Both
    # default to None and then trace the exact pre-fault graph.
    upload_src = stacked_new if stacked_upload is None else stacked_upload
    with jax.named_scope("feddd_encode_wire"):
        stacked_agg = wire_quant.quantize_dequantize_stacked(
            upload_src, rng, comm.qbits)
        wire_oh = _wire_overhead(masks, stacked_new, comm,
                                 sel_cfg.channel_axis, dense_masks)
        agg_masks = (masks if delivered is None
                     else aggregation.truncate_masks_to_prefix(masks,
                                                               delivered))
    with jax.named_scope("feddd_aggregate"):
        new_global = aggregation.aggregate_sparse_stacked(
            stacked_agg, agg_masks, weights, prev_global=global_params,
            use_kernel=sel_cfg.use_kernel, robust=robust)
    with jax.named_scope("feddd_client_update"):
        if full_round:
            new_clients = _adopt_global(new_global, stacked_new)
        else:
            # Eq. (5): the un-stacked global broadcasts against the
            # (N, ...) stacked leaves, so the per-client rule applies
            # verbatim.
            new_clients = aggregation.client_update_sparse(
                new_global, stacked_new, masks)
    return RoundOutputs(new_clients, new_global, density, wire_oh)


@dataclasses.dataclass
class BatchedRoundEngine:
    """One-jit-call FedDD round over client-stacked parameters.

    Args:
      selection_cfg: mask-building config; ``selection_cfg.use_kernel``
        routes BOTH the importance scoring and the Eq. (4) aggregation
        through the Pallas kernels.
      comm: wire-format config (repro.comm).  Non-default codecs add the
        measured mask/scale overhead to the step outputs; ``qbits < 32``
        quantizes the values the aggregation consumes.  The default is
        bit-identical to a comm-less engine.
      robust_agg: Eq. (4) variant — ``"mean"`` (default, bit-identical
        to the pre-robust engine), ``"trimmed[:beta]"`` coordinate-wise
        trimmed mean, ``"clip[:factor]"`` per-client norm clipping
        (repro.core.aggregation module docstring).  Static: each variant
        compiles its own fused step.
    """

    selection_cfg: selection.SelectionConfig = dataclasses.field(
        default_factory=selection.SelectionConfig)
    comm: CommConfig = dataclasses.field(default_factory=CommConfig)
    robust_agg: str = "mean"

    def step(self, stacked_old, stacked_new, global_params,
             dropout_rates, weights, rng, *, full_round: bool,
             dense_masks: bool = False, stacked_upload=None,
             delivered=None) -> RoundOutputs:
        """Run one round's server side.

        Args:
          stacked_old / stacked_new: client params before/after local
            training, leaves (N, *leaf).
          global_params: current global pytree (un-stacked).
          dropout_rates: (N,) float32 D_n^t.
          weights: (N,) aggregation weights m_n (sample counts).  A zero
            weight excludes that client from the Eq. (4) aggregate — this
            is how baseline non-participants, deadline-dropped stragglers
            (sim/policies.py), and staleness-decayed async merges ride the
            same fused step.
          rng: the ROUND key (same key the per-client loop splits from).
          full_round: t mod h == 0 — dense broadcast round (static: the two
            variants compile once each).
          dense_masks: all-ones masks / full uploads (the fedavg / fedcs /
            oort baselines); skips importance scoring entirely (static).
          stacked_upload: optional stacked pytree the AGGREGATION consumes
            instead of ``stacked_new`` — the on-wire rendering when fault
            injection corrupts uploads (clients' own Eq. (5) state stays
            ``stacked_new``).
          delivered: optional per-mask-leaf (N,) int32 delivered-channel
            counts; truncates each client's aggregation mask to its
            delivered prefix (deadline partial aggregation).
        """
        return _round_step(
            stacked_old, stacked_new, global_params,
            jnp.asarray(dropout_rates, jnp.float32),
            jnp.asarray(weights, jnp.float32), rng, stacked_upload,
            delivered, sel_cfg=self.selection_cfg,
            full_round=bool(full_round),
            dense_masks=bool(dense_masks), comm=self.comm,
            robust=str(self.robust_agg))

    def run(self, state: ScanState, telemetry: ScanTelemetry, *,
            num_rounds: int, batched_train_fn, weights,
            h: int, a_server: float, d_max: float, delta: float,
            global_model_bytes: float, t_start=1, scheme: str = "feddd",
            static_participants=None, oort_penalty=None,
            oort_budget: float = 0.0, alloc_iters: int = 96,
            donate: bool = True) -> Tuple[ScanState, ScanTrace]:
        """Run ``num_rounds`` FULL rounds — training, masks, Eq. (4)
        aggregation, Eq. (5)/(6) updates, the Eq. (9)-(11) dropout-rate
        re-allocation AND the Eq. (12) clock — as ONE ``lax.scan`` device
        dispatch.

        Each scanned round reproduces :meth:`step` fed the same carry —
        learning state bit-identical, allocator output pinned to
        float32-ulp scale (the protocol's chunked executor and
        tests/test_round_engine.py hold the contract); the win is that K
        rounds cost one Python dispatch and one host transfer (the
        stacked :class:`ScanTrace`) instead of K of each.

        Args:
          state: the :class:`ScanState` carry entering round ``t_start``.
          telemetry: static :class:`ScanTelemetry` (allocator + clock
            inputs).
          num_rounds: K, the chunk length (static: one compile per K).
          batched_train_fn: ``(stacked_params, round_key) ->
            (stacked_params, (N,) losses)`` — local training must be
            device-fused for the loop to scan.  Pass it ``jax.jit``-wrapped
            (callers already do — jit-of-jit just inlines): per-round
            dispatch then runs the same XLA-compiled arithmetic the scan
            inlines, which is what makes scanned rounds bit-identical to
            sequential ones.  An eager train fn is still correct but can
            differ from its compiled self in the last float32 bit
            (e.g. fused multiply-adds).
          weights: (N,) aggregation weights m_n (sample counts).
          h / a_server / d_max / delta / global_model_bytes: protocol
            constants (static).
          t_start: 1-based round index of the chunk's first round (traced:
            successive chunks reuse the compile).
          scheme: "feddd" runs masks + re-allocation; the dense baselines
            ("fedavg" / "fedcs" / "oort") run full uploads with
            non-participants masked back to stale params/losses.
          static_participants: (N,) bool — required for "fedcs", whose
            loss-independent selection is precomputed host-side.
          oort_penalty / oort_budget: required for "oort" — the static
            system-utility penalty (:func:`repro.core.baselines
            .oort_system_penalty`) and the byte budget for the traced
            greedy re-ranking.
          alloc_iters: golden-section iterations of the in-scan allocator
            (96 matches ``solve_dropout_rates_with``'s default, so the
            scanned rates are bit-identical to the sequential
            ``allocator="jax"`` path).
          donate: donate the STACKED PARAMS and GLOBAL PARAMS carries to
            the dispatch (``donate_argnums`` on the ``client_params`` and
            ``global_params`` arguments — the losses / rng / clock stay
            un-donated, they are tiny and may alias caller arrays) so both
            model buffers update in place instead of being copied per
            chunk.  XLA implements the donation on CPU/GPU/TPU for the
            pinned jax version; a backend that declines falls back to a
            copy with a compile-time warning.  The caller must treat BOTH
            passed-in carries as consumed — the protocol executor copies
            the user-provided global pytree once before its first chunk so
            the caller's arrays are never invalidated
            (tests/test_round_engine.py
            ::test_scanned_run_donates_stacked_carry pins all sides).
        """
        if scheme == "fedcs" and static_participants is None:
            raise ValueError("scheme='fedcs' requires static_participants")
        if scheme == "oort" and oort_penalty is None:
            raise ValueError("scheme='oort' requires oort_penalty (see "
                             "baselines.oort_system_penalty) + oort_budget")
        n = telemetry.model_bytes.shape[0]
        spec = (None if self.comm.is_default else WireSpec.from_stacked(
            state.client_params, self.selection_cfg.channel_axis))
        fn = _scanned_rounds_fn(
            batched_train_fn, self.selection_cfg, int(num_rounds), int(h),
            str(scheme), float(a_server), float(d_max), float(delta),
            float(global_model_bytes), int(alloc_iters), bool(donate),
            self.comm, spec, str(self.robust_agg))
        part = (jnp.ones((n,), bool) if static_participants is None
                else jnp.asarray(static_participants, bool))
        pen = (jnp.ones((n,), jnp.float32) if oort_penalty is None
               else jnp.asarray(oort_penalty, jnp.float32))
        return fn(state.client_params, state.global_params,
                  tuple(state)[2:], telemetry,
                  jnp.asarray(t_start, jnp.int32),
                  jnp.asarray(weights, jnp.float32), part, pen,
                  jnp.asarray(oort_budget, jnp.float32))


# One compiled fn per (train fn, selection config, chunk length, protocol
# constants): the module-level cache is shared across engine instances and
# protocol runs, and t_start stays traced so successive chunks of the same
# length never retrace.
@functools.lru_cache(maxsize=64)
def _scanned_rounds_fn(train_fn, sel_cfg: selection.SelectionConfig,
                       num_rounds: int, h: int, scheme: str,
                       a_server: float, d_max: float, delta: float,
                       global_model_bytes: float, alloc_iters: int,
                       donate: bool, comm: CommConfig,
                       wire_spec, robust: str = "mean"):
    dense = scheme != "feddd"

    # client_params and global_params are separate leading arguments so
    # donate_argnums can target exactly the two model-buffer carries: the
    # losses / rng / clock entries of the state are tiny, may alias
    # caller-visible arrays, and are never donated.  The protocol executor
    # copies the user-provided global pytree once before its first chunk,
    # so donating the global carry never invalidates caller state.
    def run_rounds(client_params, global_params, rest: Tuple,
                   tel: ScanTelemetry, t_start,
                   weights, static_part, oort_penalty, oort_budget):
        state = ScanState(client_params, global_params, *rest)
        n = weights.shape[0]

        def body(st: ScanState, t):
            params, gparams, losses, dropout, rng, sim_time = st
            rng, rk = jax.random.split(rng)
            d_used = dropout
            # participation — the only scheme whose selection is both
            # dynamic and loss-dependent (oort) re-ranks in-trace
            with jax.named_scope("feddd_select"):
                if scheme == "fedcs":
                    part = static_part
                elif scheme == "oort":
                    part = baselines.select_oort_traced(
                        losses, num_samples=tel.num_samples,
                        system_penalty=oort_penalty,
                        model_bytes=tel.model_bytes, budget=oort_budget)
                else:                    # feddd / fedavg: everyone
                    part = jnp.ones((n,), bool)
            # jax.named_scope: compile-time operator-name metadata only
            # (repro.obs phase vocabulary in HLO / profiler traces); the
            # compiled program is independent of observability settings.
            with jax.named_scope("feddd_local_train"):
                stacked_new, loss_dev = train_fn(params, rk)
                loss_dev = jnp.asarray(loss_dev, jnp.float32)
            with jax.named_scope("feddd_encode_masks"):
                if dense:
                    # Non-participants must not train this round: the
                    # vmapped trainer computed every row, participation
                    # masks the results back to stale params/losses
                    # (exactly the per-round executor's rule).
                    pexp = part.reshape
                    stacked_new = jax.tree_util.tree_map(
                        lambda new, old: jnp.where(
                            pexp((-1,) + (1,) * (new.ndim - 1)), new, old),
                        stacked_new, params)
                    loss_dev = jnp.where(part, loss_dev, losses)
                    masks, density = _dense_masks(stacked_new, n)
                else:
                    masks, density = selection.build_masks_batched(
                        params, stacked_new, d_used, config=sel_cfg,
                        rng=rk)
            # wire format: same static branches as _round_step — the
            # server aggregates the decoded (possibly quantized) uploads
            # and the measured mask/scale overhead rides the trace
            with jax.named_scope("feddd_encode_wire"):
                stacked_agg = wire_quant.quantize_dequantize_stacked(
                    stacked_new, rk, comm.qbits)
                wire_oh = _wire_overhead(masks, stacked_new, comm,
                                         sel_cfg.channel_axis, dense)
            with jax.named_scope("feddd_aggregate"):
                new_global = aggregation.aggregate_sparse_stacked(
                    stacked_agg, masks, weights * part,
                    prev_global=gparams, use_kernel=sel_cfg.use_kernel,
                    robust=robust)
            with jax.named_scope("feddd_client_update"):
                if dense:
                    new_clients = _adopt_global(new_global, stacked_new)
                else:
                    # t is traced inside the scan, so the Eq. (5)/(6)
                    # choice is a ``lax.cond`` over the round index — one
                    # branch executes per round (the sequential step's two
                    # static compiles become the conditional's two arms).
                    # A masked select would be wrong-by-ulp anyway: Eq. (5)
                    # with an all-ones mask computes g*1 + l*0, and
                    # -0.0 + 0.0 is +0.0, flipping signed zeros vs the
                    # adopt-global copy.
                    full = (t % h) == 0
                    new_clients = lax.cond(
                        full,
                        lambda g, l, m: _adopt_global(g, l),
                        aggregation.client_update_sparse,
                        new_global, stacked_new, masks)
            # Step 5: dropout-rate re-allocation for round t+1 (feddd).
            # The f32 clip mirrors the host dispatcher's float64 clip —
            # both feed the next round the same f32 rates.
            with jax.named_scope("feddd_allocate"):
                if dense:
                    d_next = jnp.zeros_like(dropout)
                    d_time = jnp.zeros_like(dropout)
                else:
                    # The solver self-fences with optimization_barrier
                    # (see its docstring), so inlining it here returns
                    # the same bits as the per-round host dispatch.
                    d_next, _ = allocation.solve_dropout_rates_jax(
                        *tel, jnp.maximum(loss_dev, 1e-6),
                        a_server=a_server, d_max=d_max, delta=delta,
                        global_model_bytes=global_model_bytes,
                        num_iters=alloc_iters)
                    d_next = jnp.clip(d_next, 0.0, d_max)
                    d_time = d_used
            # Eq. (12) round clock over participating clients, using the
            # dropout the uploads actually used (device f32 axis).  A
            # non-dense codec charges its analytic byte model on the
            # uplink leg — the same model the host-side driver charges —
            # while the downlink broadcast stays on the idealized mass.
            with jax.named_scope("feddd_clock"):
                u_eff = tel.model_bytes * (1.0 - d_time)
                if comm.is_default or wire_spec is None:
                    up_bytes = u_eff
                else:
                    up_bytes = analytic_wire_bytes(wire_spec, d_time,
                                                   comm, xp=jnp)
                t_all = (tel.compute_latency
                         + up_bytes / tel.uplink_rate
                         + u_eff / tel.downlink_rate)
                round_t = jnp.max(jnp.where(part, t_all, -jnp.inf))
                sim_time = sim_time + round_t
            st2 = ScanState(new_clients, new_global, loss_dev, d_next,
                            rng, sim_time)
            return st2, ScanTrace(loss_dev, density, d_next, part,
                                  round_t, sim_time, wire_oh)

        ts = t_start + jnp.arange(num_rounds, dtype=jnp.int32)
        return jax.lax.scan(body, state, ts)

    return jax.jit(run_rounds, donate_argnums=(0, 1) if donate else ())


# ------------------------------------------- client-sharded engine (SPMD)

def _leaf_sharded_reduce(num, den, gprev, dtype, *, channel_axis: int,
                         collective: str, keep_fraction: float,
                         axis_name: str):
    """Cross-shard Eq. (4) reduction of one leaf's (num, den) partials.

    ``collective="dense"``: a plain psum — exact, and on a 1-device mesh
    the identity, which is what makes the sharded engine bit-identical to
    the fused single-device step there.

    ``collective="sparse"``: the channel axis moves to the front, the
    denominator collapses to its (C,) channel profile (channel-structured
    masks make den constant along every other axis), and the partials ride
    :func:`repro.core.sparse_collective.sparse_numden_allreduce` — each
    shard ships only its top-``K = ceil(C * keep_fraction)`` channels by
    den mass plus int32 indices.  A channel with zero den has exactly-zero
    num rows, so the compaction is lossless whenever a shard's nonzero
    channel count fits the buffer; the returned overflow counts channels
    that did not.

    Returns (aggregated leaf, overflow scalar f32).
    """
    zero = jnp.float32(0.0)
    ndim = num.ndim
    ax = channel_axis % ndim if ndim else 0
    c = num.shape[ax] if ndim else 1
    if collective == "sparse" and ndim >= 1 and c > 1:
        num_cm = jnp.moveaxis(num, ax, 0)
        den_ch = jnp.moveaxis(den, ax, 0).reshape((c, -1))[:, 0]
        k = max(1, min(c, int(math.ceil(c * keep_fraction))))
        nnz = jnp.sum((den_ch > 0).astype(jnp.int32))
        num_tot_cm, den_ch_tot, ovf = \
            sparse_collective.sparse_numden_allreduce(
                num_cm, den_ch, k, axis_name, k_local=nnz)
        num_tot = jnp.moveaxis(num_tot_cm, 0, ax)
        dshape = [1] * ndim
        dshape[ax] = c
        den_tot = jnp.broadcast_to(den_ch_tot.reshape(dshape), num.shape)
        return (aggregation.finish_masked_mean(num_tot, den_tot, gprev,
                                               dtype), ovf)
    num_tot = jax.lax.psum(num, axis_name)
    den_tot = jax.lax.psum(den, axis_name)
    return (aggregation.finish_masked_mean(num_tot, den_tot, gprev, dtype),
            zero)


# One compiled fn per (mesh, selection config, round kind, comm,
# collective) — module-level cache shared across engine instances, like
# ``_round_step``'s jit cache.  Mesh objects hash on their device grid +
# axis names, so re-constructed identical meshes share the entry.
@functools.lru_cache(maxsize=64)
def _sharded_step_fn(mesh, sel_cfg: selection.SelectionConfig,
                     full_round: bool, dense_masks: bool,
                     comm: CommConfig, collective: str,
                     keep_fraction: float, robust: str = "mean"):
    p_c = jax.sharding.PartitionSpec("clients")
    p_r = jax.sharding.PartitionSpec()
    axis = "clients"
    r_kind, r_arg = aggregation.parse_robust_agg(robust)

    def body(stacked_old, stacked_new, global_params, dropout, weights,
             ids, rng):
        n_s = ids.shape[0]
        # Shard-local phases are the SAME traced arithmetic as
        # ``_round_step``: masks + QDQ fold the GLOBAL fleet positions
        # (``ids``), so every client's RNG stream is independent of how
        # the fleet is sharded.
        with jax.named_scope("feddd_encode_masks"):
            if dense_masks:
                masks, density = _dense_masks(stacked_new, n_s)
            else:
                masks, density = selection.build_masks_batched(
                    stacked_old, stacked_new, dropout, config=sel_cfg,
                    rng=rng, client_indices=ids)
        with jax.named_scope("feddd_encode_wire"):
            stacked_agg = wire_quant.quantize_dequantize_stacked(
                stacked_new, rng, comm.qbits, client_indices=ids)
            wire_oh = _wire_overhead(masks, stacked_new, comm,
                                     sel_cfg.channel_axis, dense_masks)
            if wire_oh is None:
                wire_oh = jnp.zeros((n_s,), jnp.int32)
        with jax.named_scope("feddd_aggregate"):
            g_leaves, treedef = jax.tree_util.tree_flatten(global_params)
            w_leaves = jax.tree_util.tree_leaves(stacked_agg)
            m_leaves = jax.tree_util.tree_leaves(masks)
            overflow = jnp.float32(0.0)
            if r_kind != "mean":
                # Robust variants need cross-client order statistics /
                # whole-tree norms, which shard-local (num, den) partials
                # cannot compose — dense-gather fallback: all_gather the
                # client axis (device order = fleet order) and run the
                # single-device robust reduction replicated on every
                # shard, so the result is the same arithmetic as the
                # batched engine's.
                sw_full = [jax.lax.all_gather(sw, axis, tiled=True)
                           for sw in w_leaves]
                sm_full = [jax.lax.all_gather(
                    jnp.broadcast_to(sm, sw.shape), axis, tiled=True)
                    for sw, sm in zip(w_leaves, m_leaves)]
                w_full = jax.lax.all_gather(weights, axis, tiled=True)
                out_leaves = aggregation.robust_leaf_stacks(
                    sw_full, sm_full, w_full, g_leaves, r_kind, r_arg,
                    sel_cfg.use_kernel)
            else:
                out_leaves = []
                for sw, sm, gl in zip(w_leaves, m_leaves, g_leaves):
                    bm = jnp.broadcast_to(sm, sw.shape)
                    num, den = aggregation.leaf_masked_partials(
                        sw, bm, weights, sel_cfg.use_kernel)
                    agg, ovf = _leaf_sharded_reduce(
                        num, den, gl, sw.dtype,
                        channel_axis=sel_cfg.channel_axis,
                        collective=collective,
                        keep_fraction=keep_fraction,
                        axis_name=axis)
                    overflow = overflow + ovf
                    out_leaves.append(agg)
            new_global = jax.tree_util.tree_unflatten(treedef, out_leaves)
        with jax.named_scope("feddd_client_update"):
            if full_round:
                new_clients = _adopt_global(new_global, stacked_new)
            else:
                new_clients = aggregation.client_update_sparse(
                    new_global, stacked_new, masks)
        return new_clients, new_global, density, wire_oh, overflow

    # check_vma=False: the replicated outputs (new_global, overflow) are
    # replicated BY CONSTRUCTION — psum / identical all_gather+scatter on
    # every shard — but the static replication checker cannot prove it
    # through the scatter-adds of the sparse path.
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(p_c, p_c, p_r, p_c, p_c, p_c, p_r),
                       out_specs=(p_c, p_r, p_c, p_c, p_r),
                       check_vma=False)
    return jax.jit(fn)


def _pad_rows(stacked, pad: int):
    """Append ``pad`` zero rows along the leading client axis."""
    return jax.tree_util.tree_map(
        lambda l: jnp.concatenate(
            [l, jnp.zeros((pad,) + l.shape[1:], l.dtype)]), stacked)


@dataclasses.dataclass
class ShardedRoundEngine:
    """Client-sharded FedDD round over a 1-D ``clients`` device mesh.

    The fleet's client axis shards over ``mesh``; per-shard mask building,
    wire encoding, Eq. (4) partials, and Eq. (5)/(6) updates run inside
    ONE ``shard_map`` so each device only ever touches its ``N/P`` rows.
    The sole cross-device traffic is the Eq. (4) (num, den) reduction —
    dense psum by default, or the compacted top-K channel exchange of
    ``core/sparse_collective.py`` (``collective="sparse"``), whose
    per-link bytes scale with (1-D).

    Contracts (tests/test_sharded_engine.py):
      * on a 1-device mesh with ``collective="dense"`` the step is
        BIT-IDENTICAL to :class:`BatchedRoundEngine` — same RNG folds
        (global fleet ids), same partial sums, psum = identity;
      * on multi-device meshes parity is allclose: psum adds per-shard
        partial sums in a different order than the single flat (N,)
        reduction, so the last float32 bit is reduction-order dependent
        (the standard SPMD ulp caveat);
      * ``collective="sparse"`` additionally reports ``overflow`` — the
        psum of channels whose den mass did not fit a shard's static
        buffer; zero overflow certifies the compacted reduction carried
        exactly the dense psum's mass.

    Clients need not divide the mesh: the trailing shard zero-pads with
    weight-0 rows (excluded from Eq. (4) by the same rule as
    non-participants) and the padded outputs are sliced off.
    """

    selection_cfg: selection.SelectionConfig = dataclasses.field(
        default_factory=selection.SelectionConfig)
    comm: CommConfig = dataclasses.field(default_factory=CommConfig)
    mesh: object = None        # jax.sharding.Mesh with a "clients" axis
    collective: str = "dense"  # dense psum | sparse compacted top-K
    keep_fraction: float = 1.0  # sparse buffer: K = ceil(C * fraction)
    robust_agg: str = "mean"   # Eq. (4) variant; non-mean falls back to
                               # a dense all-gather of the client axis

    def __post_init__(self):
        if self.mesh is None:
            raise ValueError("ShardedRoundEngine requires a mesh (see "
                             "repro.launch.mesh.make_client_mesh)")
        if "clients" not in self.mesh.axis_names:
            raise ValueError(
                f"mesh must carry a 'clients' axis; got "
                f"{self.mesh.axis_names}")
        if self.collective not in ("dense", "sparse"):
            raise ValueError(f"collective must be 'dense' or 'sparse', "
                             f"got {self.collective!r}")
        if not 0.0 < self.keep_fraction <= 1.0:
            raise ValueError(f"keep_fraction must be in (0,1], got "
                             f"{self.keep_fraction}")

    @property
    def num_shards(self) -> int:
        return self.mesh.devices.size

    def step(self, stacked_old, stacked_new, global_params,
             dropout_rates, weights, rng, *, full_round: bool,
             dense_masks: bool = False, stacked_upload=None,
             delivered=None) -> RoundOutputs:
        """One sharded round step; same signature and outputs as
        :meth:`BatchedRoundEngine.step` (wire overhead is None with the
        default comm, and ``collective_overflow`` reports the sparse
        collective's missed-channel count)."""
        if stacked_upload is not None or delivered is not None:
            raise NotImplementedError(
                "upload overrides / delivered prefixes are single-device "
                "engine features (fault corruption and deadline partial "
                "aggregation do not shard)")
        n = jax.tree_util.tree_leaves(stacked_new)[0].shape[0]
        p = self.num_shards
        pad = (-n) % p
        d = jnp.asarray(dropout_rates, jnp.float32)
        w = jnp.asarray(weights, jnp.float32)
        so, sn = stacked_old, stacked_new
        if pad:
            so = _pad_rows(so, pad)
            sn = _pad_rows(sn, pad)
            d = jnp.concatenate([d, jnp.zeros((pad,), jnp.float32)])
            w = jnp.concatenate([w, jnp.zeros((pad,), jnp.float32)])
        ids = jnp.arange(n + pad, dtype=jnp.int32)
        fn = _sharded_step_fn(self.mesh, self.selection_cfg,
                              bool(full_round), bool(dense_masks),
                              self.comm, self.collective,
                              float(self.keep_fraction),
                              str(self.robust_agg))
        new_clients, new_global, density, wire_oh, overflow = fn(
            so, sn, global_params, d, w, ids, rng)
        if pad:
            new_clients = jax.tree_util.tree_map(lambda l: l[:n],
                                                 new_clients)
            density = density[:n]
            wire_oh = wire_oh[:n]
        return RoundOutputs(new_clients, new_global, density,
                            None if self.comm.is_default else wire_oh,
                            overflow)

    def shard_spec(self):
        """NamedSharding that places a client-stacked pytree's rows on
        their shards (device_put the persistent stacked state with this so
        jit dispatches never re-shard host arrays)."""
        return jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec("clients"))


# --------------------------------------------------- shape-grouped engine

def _slice_leaf(g: jax.Array, local_shape) -> jax.Array:
    """HeteroFL width slicing: the leading [0:s) block of every axis."""
    if tuple(g.shape) == tuple(local_shape):
        return g
    return g[tuple(slice(0, s) for s in local_shape)]


def slice_pytree(global_params, local_template):
    """Slice a full-width pytree down to a sub-model's local widths."""
    return jax.tree_util.tree_map(
        lambda g, l: _slice_leaf(g, l.shape), global_params, local_template)


@functools.partial(jax.jit,
                   static_argnames=("sel_cfg", "full_round", "dense_masks",
                                    "comm", "robust"))
def _grouped_round_step(groups: Tuple[GroupBatch, ...], global_params,
                        weights, rng, *,
                        sel_cfg: selection.SelectionConfig,
                        full_round: bool,
                        dense_masks: bool = False,
                        comm: CommConfig = CommConfig(),
                        robust: str = "mean") -> GroupedRoundOutputs:
    n = weights.shape[0]
    group_masks, group_agg, group_idx = [], [], []
    densities = jnp.zeros((n,), jnp.float32)
    wire_oh = None if comm.is_default else jnp.zeros((n,), jnp.int32)
    # jax.named_scope blocks: compile-time operator-name metadata only
    # (repro.obs phase vocabulary) — the program is independent of
    # observability settings.
    with jax.named_scope("feddd_encode_masks"):
        for g in groups:
            if dense_masks:
                ng = g.indices.shape[0]
                masks = jax.tree_util.tree_map(
                    lambda l: jnp.ones((ng,) + (1,) * (l.ndim - 1),
                                       l.dtype),
                    g.stacked_new)
                dens = jnp.ones((ng,), jnp.float32)
            else:
                masks, dens = selection.build_masks_batched(
                    g.stacked_old, g.stacked_new,
                    jnp.asarray(g.dropout, jnp.float32), config=sel_cfg,
                    rng=rng, coverage=g.coverage,
                    client_indices=g.indices)
            group_masks.append(masks)
            # wire format: the aggregate consumes the decoded (possibly
            # quantized) uploads; per-member keys fold the FLEET
            # positions, matching the per-client loop (repro.comm
            # .quantize)
            group_agg.append(wire_quant.quantize_dequantize_stacked(
                g.stacked_new, rng, comm.qbits,
                client_indices=g.indices))
            group_idx.append(g.indices)
            densities = densities.at[g.indices].set(dens)
            if wire_oh is not None:
                wire_oh = wire_oh.at[g.indices].set(_wire_overhead(
                    masks, g.stacked_new, comm, sel_cfg.channel_axis,
                    dense_masks))
    with jax.named_scope("feddd_aggregate"):
        new_global = aggregation.aggregate_sparse_grouped(
            group_agg, group_masks, group_idx, weights, global_params,
            prev_global=global_params, use_kernel=sel_cfg.use_kernel,
            robust=robust)
    with jax.named_scope("feddd_client_update"):
        new_group_params = []
        for g, masks in zip(groups, group_masks):
            g_local = slice_pytree(new_global,
                                   unstack_pytree(g.stacked_new, 1)[0])
            if full_round:
                # Eq. (6): every member adopts its slice of the fresh
                # global.
                upd = jax.tree_util.tree_map(
                    lambda gl, l: jnp.broadcast_to(gl, l.shape)
                    .astype(l.dtype),
                    g_local, g.stacked_new)
            else:
                # Eq. (5): the local-width global broadcasts over the
                # group axis.
                upd = aggregation.client_update_sparse(
                    g_local, g.stacked_new, masks)
            new_group_params.append(upd)
    return GroupedRoundOutputs(tuple(new_group_params), new_global,
                               densities, wire_oh)


# One compiled grouped-sharded fn per (mesh, selection config, round kind,
# comm, shape census) — jit keyed like ``_grouped_round_step`` plus the
# static mesh.
@functools.partial(jax.jit,
                   static_argnames=("sel_cfg", "full_round", "dense_masks",
                                    "comm", "mesh"))
def _sharded_grouped_round_step(groups: Tuple[GroupBatch, ...],
                                global_params, weights_ext, rng, *,
                                sel_cfg: selection.SelectionConfig,
                                full_round: bool,
                                dense_masks: bool = False,
                                comm: CommConfig = CommConfig(),
                                mesh=None) -> GroupedRoundOutputs:
    """Grouped round with every group's MEMBER axis sharded over a 1-D
    ``clients`` mesh.

    Per group, one ``shard_map`` runs the shard-local phases (masks at
    native widths, wire encoding, Eq. (4) partials zero-padded to global
    widths) and psums the group's (num, den); the group partials then add
    across groups — Eq. (4)'s sums are linear, so group-then-total
    summation is exact up to float reduction order — before one shared
    :func:`repro.core.aggregation.finish_masked_mean`.  Eq. (5)/(6)
    updates stay row-parallel GSPMD ops over the sharded member stacks.

    ``weights_ext`` is the (N+1,) fleet weight vector with a ZERO sentinel
    at row N: callers pad each group's member axis to a mesh multiple with
    zero rows carrying canvas id N, so padded rows weigh nothing and their
    densities land on the sliced-off sentinel row.  Returns canvases of
    width N (the sentinel row is sliced before returning).
    """
    p_c = jax.sharding.PartitionSpec("clients")
    p_r = jax.sharding.PartitionSpec()
    n1 = weights_ext.shape[0]                # N + 1 (sentinel)
    g_leaves, treedef = jax.tree_util.tree_flatten(global_params)
    global_shapes = tuple(l.shape for l in g_leaves)     # static
    num_tot = [jnp.zeros(s, jnp.float32) for s in global_shapes]
    den_tot = [jnp.zeros(s, jnp.float32) for s in global_shapes]
    densities = jnp.zeros((n1,), jnp.float32)
    wire_oh = None if comm.is_default else jnp.zeros((n1,), jnp.int32)
    staged = []                              # (group, masks, dens, oh)

    for g in groups:
        def body(old, new, dropout, w_rows, ids, cov, rng):
            m = ids.shape[0]
            with jax.named_scope("feddd_encode_masks"):
                if dense_masks:
                    masks = jax.tree_util.tree_map(
                        lambda l: jnp.ones((m,) + (1,) * (l.ndim - 1),
                                           l.dtype), new)
                    dens = jnp.ones((m,), jnp.float32)
                else:
                    masks, dens = selection.build_masks_batched(
                        old, new, dropout, config=sel_cfg, rng=rng,
                        coverage=cov, client_indices=ids)
            with jax.named_scope("feddd_encode_wire"):
                agg = wire_quant.quantize_dequantize_stacked(
                    new, rng, comm.qbits, client_indices=ids)
                oh = _wire_overhead(masks, new, comm,
                                    sel_cfg.channel_axis, dense_masks)
                if oh is None:
                    oh = jnp.zeros((m,), jnp.int32)
            with jax.named_scope("feddd_aggregate"):
                nums, dens_l = [], []
                for sw, sm, gshape in zip(
                        jax.tree_util.tree_leaves(agg),
                        jax.tree_util.tree_leaves(masks), global_shapes):
                    bm = jnp.broadcast_to(sm, sw.shape)
                    num, den = aggregation.leaf_masked_partials(
                        sw, bm, w_rows, sel_cfg.use_kernel)
                    pads = [(0, gs - ls)
                            for gs, ls in zip(gshape, num.shape)]
                    num = jnp.pad(num, pads)
                    den = jnp.pad(den, pads)
                    nums.append(jax.lax.psum(num, "clients"))
                    dens_l.append(jax.lax.psum(den, "clients"))
            return masks, dens, oh, tuple(nums), tuple(dens_l)

        w_rows = weights_ext[g.indices]
        masks, dens, oh, nums, dens_l = jax.shard_map(
            body, mesh=mesh,
            in_specs=(p_c, p_c, p_c, p_c, p_c, p_r, p_r),
            out_specs=(p_c, p_c, p_c, p_r, p_r),
            check_vma=False)(g.stacked_old, g.stacked_new,
                             g.dropout, w_rows, g.indices, g.coverage,
                             rng)
        num_tot = [a + b for a, b in zip(num_tot, nums)]
        den_tot = [a + b for a, b in zip(den_tot, dens_l)]
        staged.append((g, masks, dens, oh))

    out_leaves = [aggregation.finish_masked_mean(num, den, gl, gl.dtype)
                  for num, den, gl in zip(num_tot, den_tot, g_leaves)]
    new_global = jax.tree_util.tree_unflatten(treedef, out_leaves)

    with jax.named_scope("feddd_client_update"):
        new_group_params = []
        for g, masks, dens, oh in staged:
            densities = densities.at[g.indices].set(dens)
            if wire_oh is not None:
                wire_oh = wire_oh.at[g.indices].set(oh)
            g_local = slice_pytree(new_global,
                                   unstack_pytree(g.stacked_new, 1)[0])
            if full_round:
                upd = jax.tree_util.tree_map(
                    lambda gl, l: jnp.broadcast_to(gl, l.shape)
                    .astype(l.dtype),
                    g_local, g.stacked_new)
            else:
                upd = aggregation.client_update_sparse(
                    g_local, g.stacked_new, masks)
            new_group_params.append(upd)
    return GroupedRoundOutputs(tuple(new_group_params), new_global,
                               densities[:-1],
                               None if wire_oh is None else wire_oh[:-1])


@dataclasses.dataclass
class GroupedRoundEngine:
    """One-jit-call FedDD round over a shape-grouped ragged fleet.

    The heterogeneous counterpart of :class:`BatchedRoundEngine`: clients
    are partitioned by sub-model shape (``repro.fl.heterogeneity
    .group_by_shape``), each group's parameters stack along a leading member
    axis, and ONE jit-compiled step per shape census runs, for every group,

        coverage-aware batched mask building (Eq. (20)/(21) scores at the
        group's NATIVE widths — no padded waste),
        the scatter of each group's masked update into the full-width
        aggregation canvas (:func:`repro.core.aggregation
        .aggregate_sparse_grouped`, bit-identical to the padded loop), and
        the Eq. (5)/(6) client updates at local widths.

    Group membership (``GroupBatch.indices``) is traced, so deadline drops,
    async buffers, and re-grouped fleets with the same shape census reuse
    the compiled step; a new census (different group shapes/sizes) compiles
    once.  Exclusion and staleness enter exactly as in the homogeneous
    engine: per-client weights on the stacked Eq. (4) aggregation, indexed
    by canvas row.

    With ``mesh`` (a 1-D ``clients`` device mesh) each group's MEMBER axis
    shards over the devices: shard-local masks/partials per group inside
    ``shard_map``, per-group psum of the Eq. (4) (num, den), group partials
    summed before one shared division (see
    :func:`_sharded_grouped_round_step`).  Parity with the single-device
    grouped step is allclose (per-group-then-total summation reorders the
    float reduction); clients need not divide the mesh — padded member
    rows carry weight 0 via the sentinel canvas row.
    """

    selection_cfg: selection.SelectionConfig = dataclasses.field(
        default_factory=selection.SelectionConfig)
    comm: CommConfig = dataclasses.field(default_factory=CommConfig)
    mesh: object = None        # optional jax.sharding.Mesh ("clients")
    robust_agg: str = "mean"   # Eq. (4) variant (single-device only:
                               # the sharded-grouped step composes
                               # per-group psums, which robust variants
                               # cannot ride)

    def __post_init__(self):
        if self.mesh is not None and "clients" not in self.mesh.axis_names:
            raise ValueError(
                f"mesh must carry a 'clients' axis; got "
                f"{self.mesh.axis_names}")
        if self.mesh is not None and str(self.robust_agg) != "mean":
            raise NotImplementedError(
                "robust_agg is a single-device grouped-engine feature: "
                "the sharded-grouped step sums per-group (num, den) "
                "partials across shards, which trimmed/clip aggregation "
                "cannot compose with")

    def step(self, groups: Sequence[GroupBatch], global_params,
             weights, rng, *, full_round: bool,
             dense_masks: bool = False) -> GroupedRoundOutputs:
        """Run one round's server side over the grouped fleet.

        Args:
          groups: one :class:`GroupBatch` per shape group; ``indices`` are
            rows into ``weights`` / the densities canvas AND the ids the
            per-client mask keys fold in (fleet positions for protocol/wave
            runs; buffer positions for async merges).
          global_params: current full-width global pytree.
          weights: (N,) aggregation weights m_n indexed by canvas row; zero
            excludes that row (non-participation, deadline drops,
            staleness-decayed async merges).
          rng: the ROUND key (the per-client loop's split).
          full_round / dense_masks: as in :meth:`BatchedRoundEngine.step`.
        """
        if self.mesh is None:
            return _grouped_round_step(
                tuple(groups), global_params,
                jnp.asarray(weights, jnp.float32), rng,
                sel_cfg=self.selection_cfg, full_round=bool(full_round),
                dense_masks=bool(dense_masks), comm=self.comm,
                robust=str(self.robust_agg))
        return self._step_sharded(groups, global_params, weights, rng,
                                  full_round=full_round,
                                  dense_masks=dense_masks)

    def _step_sharded(self, groups, global_params, weights, rng, *,
                      full_round: bool, dense_masks: bool
                      ) -> GroupedRoundOutputs:
        p = self.mesh.devices.size
        w = jnp.asarray(weights, jnp.float32)
        n = w.shape[0]
        w_ext = jnp.concatenate([w, jnp.zeros((1,), jnp.float32)])
        padded, sizes = [], []
        for g in groups:
            n_g = jax.tree_util.tree_leaves(g.stacked_new)[0].shape[0]
            sizes.append(n_g)
            pad = (-n_g) % p
            idx = jnp.asarray(g.indices, jnp.int32)
            drop = jnp.asarray(g.dropout, jnp.float32)
            if pad:
                g = GroupBatch(
                    indices=jnp.concatenate(
                        [idx, jnp.full((pad,), n, jnp.int32)]),
                    stacked_old=_pad_rows(g.stacked_old, pad),
                    stacked_new=_pad_rows(g.stacked_new, pad),
                    coverage=g.coverage,
                    dropout=jnp.concatenate(
                        [drop, jnp.zeros((pad,), jnp.float32)]))
            else:
                g = GroupBatch(idx, g.stacked_old, g.stacked_new,
                               g.coverage, drop)
            padded.append(g)
        out = _sharded_grouped_round_step(
            tuple(padded), global_params, w_ext, rng,
            sel_cfg=self.selection_cfg, full_round=bool(full_round),
            dense_masks=bool(dense_masks), comm=self.comm, mesh=self.mesh)
        group_params = tuple(
            (jax.tree_util.tree_map(lambda l: l[:n_g], gp)
             if jax.tree_util.tree_leaves(gp)[0].shape[0] != n_g else gp)
            for gp, n_g in zip(out.group_client_params, sizes))
        return GroupedRoundOutputs(group_params, out.global_params,
                                   out.densities, out.wire_overhead)


def train_clients(stacked, indices, local_train_fn, rk, part, losses,
                  loss_dev: List, obs=NULL_RECORDER) -> List:
    """Unstack ``stacked`` (rows = fleet clients ``indices``), train each
    participant through ``local_train_fn`` and return the new per-client
    pytrees in row order; each loss lands in ``loss_dev`` at its fleet
    position.  Non-participants keep stale params and their stale loss.
    The per-client trainer loop of both the grouped and the homogeneous
    engine paths, under the ``group_unstack`` / ``client_train`` spans."""
    with obs.span("group_unstack"):
        per_client = unstack_pytree(stacked, len(indices))
    new_list = []
    for pos, i in enumerate(indices):
        if part[i]:
            with obs.span("client_train"):
                p, l = local_train_fn(per_client[pos], i,
                                      jax.random.fold_in(rk, i))
        else:
            p, l = per_client[pos], losses[i]
        new_list.append(p)
        loss_dev[i] = l
    return new_list


def train_grouped(groups, group_stacked, group_coverage, local_train_fn,
                  rk, part, losses, d_used, *, dense: bool,
                  num_clients: int, obs=NULL_RECORDER):
    """Per-client local training over grouped stacked state + GroupBatch
    assembly — the host-side half of a grouped round, shared by the
    protocol executor and the sim runner so the two stay in lockstep.

    Trains member ``i`` iff ``part[i]`` (callers pass all-ones for feddd,
    where everyone trains); non-participants keep stale params and their
    stale loss.  Returns ``(loss_dev, batches)``: per-client device losses
    in fleet order and one complete :class:`GroupBatch` per group.
    ``obs`` (a repro.obs recorder) times each group's unstack, each
    client's training and each group's restack.
    """
    loss_dev: List = [None] * num_clients
    batches: List[GroupBatch] = []
    for grp, stacked, cov in zip(groups, group_stacked, group_coverage):
        new_list = train_clients(stacked, grp.indices, local_train_fn, rk,
                                 part, losses, loss_dev, obs)
        with obs.span("group_stack"):
            batches.append(GroupBatch(
                indices=jnp.asarray(grp.indices, jnp.int32),
                stacked_old=stacked,
                stacked_new=stack_pytrees(new_list),
                coverage=None if dense else cov,
                dropout=jnp.asarray(d_used[list(grp.indices)],
                                    jnp.float32)))
    return loss_dev, batches


def unstack_groups(groups, group_stacked, num_clients: int) -> List:
    """Grouped stacked state -> per-client pytree list in fleet order."""
    params: List = [None] * num_clients
    for grp, stacked in zip(groups, group_stacked):
        for i, p in zip(grp.indices, unstack_pytree(stacked, grp.size)):
            params[i] = p
    return params


class GroupedFleetState:
    """Host-side state of a ragged fleet between grouped rounds.

    Owns the per-group stacked params (persisting across rounds — nothing
    re-stacks between them) and the train -> step -> export cycle, so the
    protocol executor and the sim runner drive the grouped engine through
    ONE implementation and cannot drift apart.
    """

    def __init__(self, groups, group_coverage, client_params,
                 selection_cfg: selection.SelectionConfig,
                 num_clients: int, comm: CommConfig = CommConfig(),
                 mesh=None, robust_agg: str = "mean"):
        self.engine = GroupedRoundEngine(selection_cfg, comm, mesh,
                                         robust_agg)
        self.groups = groups
        self.coverage = group_coverage
        self.num_clients = num_clients
        self.group_stacked = [
            stack_pytrees([client_params[i] for i in g.indices])
            for g in groups
        ]
        self._batches = None

    def train(self, local_train_fn, rk, part, losses, d_used,
              *, dense: bool, obs=NULL_RECORDER) -> List:
        """Run local training and stage this round's GroupBatches; returns
        per-client device losses (fleet order).  ``obs``: the recorder
        :func:`train_grouped` spans with."""
        loss_dev, self._batches = train_grouped(
            self.groups, self.group_stacked, self.coverage, local_train_fn,
            rk, part, losses, d_used, dense=dense,
            num_clients=self.num_clients, obs=obs)
        return loss_dev

    def step(self, global_params, weights, rk, *, full_round: bool,
             dense: bool):
        """One grouped engine step over the staged batches; returns
        ``(new_global, densities, wire_overhead)`` and rebinds the stacked
        client state (``wire_overhead`` is None with the default comm)."""
        out = self.engine.step(self._batches, global_params, weights, rk,
                               full_round=full_round, dense_masks=dense)
        self.group_stacked = list(out.group_client_params)
        return out.global_params, out.densities, out.wire_overhead

    def discard(self) -> None:
        """Drop a staged round without stepping: client params stay at
        their pre-training state (quorum-skipped rounds, sim/faults.py)."""
        self._batches = None

    @property
    def staged_batches(self):
        """The GroupBatches ``train()`` staged for the next ``step()``
        (read-only view for the sim runner's payload-validation screen)."""
        return self._batches

    def export(self) -> List:
        """Per-client pytree list in fleet order (host-side sync point)."""
        return unstack_groups(self.groups, self.group_stacked,
                              self.num_clients)


def make_batched_train_fn(per_client_step, stacked_data):
    """vmap a per-client ``step(params, *client_data) -> (params, loss)``
    into ``(stacked_params, rng) -> (stacked_params, (N,) losses)``.

    Convenience for fully-fused rounds when every client's data shard has
    the same shape (the benchmark's homogeneous setting).  ``stacked_data``
    is a tuple of arrays with a leading client axis.
    """
    def batched(stacked_params, rng):
        del rng
        return jax.vmap(per_client_step)(stacked_params, *stacked_data)

    return batched
