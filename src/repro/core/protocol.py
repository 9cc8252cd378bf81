"""FedDD training protocol — the paper's Algorithm 1, plus baseline drivers.

The driver is deliberately generic: it orchestrates *any* model exposing

    local_train_fn(params, client_data, rng) -> (new_params, loss)
    eval_fn(params) -> metrics dict            (optional)

so the same code runs the paper's MLP/CNN FL simulations and the pod-scale
transformer federation (examples/federated_pods.py uses the shard_map
collectives in core/sparse_collective.py instead, for on-device execution;
this driver is the faithful parameter-server formulation).

Round execution is a strategy behind one executor interface
(:class:`_RoundExecutor`): every strategy runs the identical Algorithm-1
maths, they differ only in how the device work is dispatched (see
tests/test_round_engine.py, tests/test_grouped_engine.py, tests/test_sim.py
for the equivalence contracts).  Routing table — which executor handles
which scenario:

==========================  =================================================
scenario                    executor
==========================  =================================================
homogeneous (any scheme)    **batched engine** (core/round_engine.py): one
                            jit-compiled device step per round; feddd may
                            pass ``batched_train_fn`` to fuse local training
                            too; fedavg / fedcs / oort run ``dense_masks``
                            mode with non-participants as 0-weights in the
                            stacked Eq. (4) aggregation
homogeneous +               **scanned engine** (core/round_engine.py
``rounds_per_dispatch>1``   BatchedRoundEngine.run): K rounds per device
                            dispatch via ``lax.scan`` — training, masks,
                            Eq. (4)/(5)/(6), the Eq. (9)-(11) re-allocation
                            AND the Eq. (12) clock all live in the scan
                            carry; ONE host transfer (the stacked
                            ScanTrace) per chunk.  Requires
                            ``allocator="jax"``, ``batched_train_fn``, and
                            no per-round ``eval_fn``; learning state is
                            bit-identical to K sequential engine rounds
                            (allocator pinned to f32-ulp scale)
heterogeneous (ragged       **grouped engine** (core/round_engine.py
widths, any scheme)         GroupedRoundEngine): clients partitioned by
                            sub-model shape (repro.fl.heterogeneity), one
                            fused step per shape census — coverage-aware
                            batched masks at native widths, one shared
                            scatter into the full-width Eq. (4) canvas,
                            local-width client updates
homogeneous +               **sharded engine** (core/round_engine.py
``mesh=``                   ShardedRoundEngine): the fleet's client axis
                            shards over a 1-D ``clients`` device mesh;
                            masks, wire encoding, Eq. (4) partials and
                            Eq. (5)/(6) updates run per shard inside one
                            ``shard_map`` and only the (num, den)
                            reduction crosses devices — dense psum
                            (default; bit-identical to the batched engine
                            on a 1-device mesh) or the compacted top-K
                            channel exchange of core/sparse_collective.py
                            (``mesh_collective="sparse"``: per-link bytes
                            scale with 1-D).  Ragged fleets with ``mesh=``
                            ride the grouped engine's sharded step (per
                            group member-axis shard_map + per-group psum).
                            The allocation LP and the Eq. (12) clock run
                            on gathered host telemetry exactly as the
                            batched row above.  Excludes
                            ``rounds_per_dispatch>1`` (the scan carries
                            single-device state)
track_epsilon, or           **reference loop**: the per-client Python loop,
``batched=False``           kept as the bit-exactness oracle (grouped and
                            batched engines are pinned against it) and for
                            the Assumption-3 epsilon estimator's per-client
                            mask pytrees
dynamic networks /          **sim runner** (repro/sim/runner.py): pass
stragglers / deadline or    ``sim=``/``network=`` to :func:`run_scheme`;
async serving               event-driven clock, observed-telemetry LP
                            re-solve, sync / deadline / async policies;
                            ragged fleets ride the grouped engine there too
faults: churn, lossy or     **sim runner + fault layer** (repro/sim/
corrupted uplinks, retry/   faults.py): pass ``faults=`` to
timeout serving, quorum     :func:`run_scheme` (with ``sim=``); crash /
degradation                 packet-loss / corruption injection, payload
                            validation + quarantine (0-weight on the same
                            stacked Eq. (4) step), the ``retry`` timeout
                            policy, deadline partial aggregation of
                            delivered mask-channel prefixes, and the
                            minimum-quorum round skip with survivor-only
                            LP re-solves.  All fault rates 0 == no fault
                            model, bit for bit (tests/test_faults.py)
observability (metrics,     **every executor** via ``ProtocolConfig(obs=
span tracing, JSONL run     ObsConfig(...))`` (repro.obs): the driver builds
logs, run-inspection CLI)   one recorder per run; host spans wrap every
                            host moment of ``run`` (the vocabulary is
                            ``repro.obs.PHASES``: fleet_stack, the round
                            phases, round_records, fleet_unstack —
                            ``Recorder.span`` in the executors below), a
                            metrics registry accumulates round / byte /
                            failure totals, and every RoundRecord lands in
                            the JSONL log as a ``round`` event (inspect
                            with ``python -m repro.obs.report``).  Byte
                            counters hook the ONE shared reduction
                            (``account_uplink(obs=...)``); everything else
                            reads the round's existing host transfer — no
                            new device->host syncs.  The default
                            ``ObsConfig()`` is inert (NULL_RECORDER): runs
                            are bit-identical with observability off, and
                            the engines' ``jax.named_scope`` phase
                            annotations are compile-time metadata, so
                            enabling it never changes compiled programs
                            (tests/test_obs.py)
Byzantine-robust            **batched / scanned / grouped / sharded
aggregation                 engines** via ``ProtocolConfig(robust_agg=
(``robust_agg``)            ...)`` (core/aggregation.py): coordinate-wise
                            trimmed mean (``"trimmed[:beta]"``) or
                            per-client update norm-clipping
                            (``"clip[:factor]"``) replace the weighted
                            mean inside the SAME fused stacked Eq. (4)
                            step — no per-client host loop.  On a mesh
                            the trimmed/clip statistics need the full
                            client axis, so the sharded step falls back
                            to a dense ``all_gather`` of the masked
                            leaves (per-link bytes scale with the fleet);
                            sharded+grouped robust is rejected.  The
                            default ``"mean"`` is bit-identical to the
                            plain engines on every path
                            (tests/test_robust_agg.py)
crash-resume                **engine + loop executors** via
(``checkpoint_every`` /     ``ProtocolConfig(checkpoint_every=K,
``resume_from``)            checkpoint_path=...)`` (repro.checkpoint):
                            every K rounds the driver atomically
                            snapshots a full :class:`RunState` — global
                            + stacked client params, PRNG key, losses,
                            dropout rates, round history — and
                            ``resume_from=`` restarts a killed run at
                            the next round with BIT-IDENTICAL RoundRecord
                            history and final params, faults and obs
                            included (fault/outage draws are keyed per
                            (seed, tag, epoch, client), so they replay
                            free; tests/test_resume.py).  The sim runner
                            checkpoints its own wave-policy state the
                            same way.  ``checkpoint_every=None``
                            (default) touches no code path
population-scale serving    **sim runner + repro.population**: pass
(``population=`` /          ``population=Population(tel, availability=...,
``cohort_size=``)           sampler=...)`` and ``cohort_size=`` to
                            :func:`run_scheme` / ``run_sim`` — telemetry
                            and the network model cover a 100k+ client
                            POPULATION, availability churn decides who is
                            online each epoch, and only the sampled
                            cohort is materialized into the stacked /
                            grouped engine buffers; sticky per-client
                            state (telemetry EWMAs by global id, losses,
                            dropout rates, Oort utilities, byte/failure
                            economy) survives cohort changes in the
                            Population store, and the Eq. (9)-(11) LP can
                            cold-start first-contact clients from
                            population means.  Population == fleet with
                            always-on availability and the default
                            sampler is bit-identical to the plain runs on
                            every engine path (tests/test_population.py)
wire formats (sparse        **every executor** via ``ProtocolConfig(comm=
codecs, quantization,       CommConfig(codec=..., qbits=...))`` (repro.comm):
on-wire byte accounting)    masks ship as packed-bitmask / delta+varint
                            index / auto encodings, values as fp32 / fp16 /
                            int8-SR; ``RoundRecord.wire_bytes`` carries the
                            measured cost next to the raw
                            ``uploaded_bytes``, the Eq. (12) uplink and the
                            sim's event timeline charge codec bytes, and
                            ``comm.overhead_aware_allocation`` solves the
                            LP on effective bytes.  Default = the analytic
                            accounting, bit for bit
==========================  =================================================

* The batched and grouped engines are bit-identical to the reference loop
  for FedDD and match it to float tolerance for the baselines (summation
  order differs).  Benchmarks: ``PYTHONPATH=src python
  benchmarks/perf_federated.py`` (homogeneous), ``PYTHONPATH=src python
  benchmarks/heterogeneous.py --perf`` (ragged).
* The sim runner with the synchronous policy over a static network
  reproduces this driver's Eq. (12) round times exactly — for homogeneous
  AND ragged fleets.

Simulated wall-clock follows the paper's system model exactly
(t = t_cmp + U(1-D)/r_u + U(1-D)/r_d; the round takes max over participating
clients, using the dropout rates the round's uploads actually used) — this
is how the paper's own simulation computes time-to-accuracy.  The closed
form is exact only for the synchronous policy; anything event-ordered
(deadlines, stragglers, async merges) lives in ``repro.sim``.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs as obs_mod
from repro.comm import codecs as wire_codecs
from repro.comm import quantize as wire_quant
from repro.comm.payload import (CommConfig, WireSpec, account_collective,
                                account_uplink, analytic_uplink_vector)
from repro.core import (aggregation, baselines, coverage as cov_mod,
                        round_engine, selection)
from repro.core.allocation import (ALLOCATORS, AllocationResult,
                                   ClientTelemetry,
                                   solve_dropout_rates_overhead_aware,
                                   solve_dropout_rates_with)
from repro.core.convergence import estimate_epsilon

Params = object  # pytree


@dataclasses.dataclass
class ProtocolConfig:
    scheme: str = "feddd"            # feddd | fedavg | fedcs | oort
    selection: selection.SelectionConfig = dataclasses.field(
        default_factory=selection.SelectionConfig)
    a_server: float = 0.6            # communication budget (Table 4)
    d_max: float = 0.8               # max dropout rate (Table 4)
    delta: float = 1.0               # heterogeneity penalty factor
    h: int = 5                       # full-broadcast period (Table 4)
    rounds: int = 50
    seed: int = 0
    track_epsilon: bool = False      # Assumption-3 estimator (costly)
    batched: bool = True             # engine-backed execution (homogeneous
                                     # batched engine / ragged grouped
                                     # engine); False forces the reference
                                     # per-client loop
    allocator: str = "numpy"         # Eq. (16)/(17) LP solver: "numpy"
                                     # (exact reference) or "jax" (jit-able
                                     # fori_loop golden section; required
                                     # by the multi-round lax.scan)
    rounds_per_dispatch: int = 1     # K>1: run K rounds as ONE lax.scan
                                     # device dispatch (homogeneous engine
                                     # + batched_train_fn + allocator="jax"
                                     # only); 1 = per-round dispatch
    comm: CommConfig = dataclasses.field(default_factory=CommConfig)
                                     # wire format (repro.comm): mask codec
                                     # + value precision + overhead-aware
                                     # allocation.  The default (dense, 32)
                                     # is the pre-comm analytic accounting,
                                     # bit for bit.
    obs: obs_mod.ObsConfig = dataclasses.field(
        default_factory=obs_mod.ObsConfig)
                                     # observability (repro.obs): metrics
                                     # registry + host spans + JSONL run
                                     # log.  The default is INERT — runs
                                     # are bit-identical with it off.
    mesh: object = None              # client-sharded SPMD execution
                                     # (core/round_engine.py
                                     # ShardedRoundEngine): an int device
                                     # count, True (all local devices), or
                                     # a jax.sharding.Mesh with a
                                     # "clients" axis.  None = the
                                     # single-device engines.
    mesh_collective: str = "dense"   # cross-shard Eq. (4) reduction:
                                     # "dense" psum (exact) or "sparse"
                                     # compacted top-K channel exchange
                                     # (core/sparse_collective.py)
    mesh_keep_fraction: float = 1.0  # sparse collective buffer size:
                                     # K = ceil(C * fraction) channels per
                                     # shard on the wire
    robust_agg: str = "mean"         # Eq. (4) aggregation variant
                                     # (core/aggregation.py): "mean"
                                     # (default; bit-identical to the
                                     # plain engines), "trimmed[:beta]"
                                     # coordinate-wise trimmed mean, or
                                     # "clip[:factor]" per-client update
                                     # norm clipping.  Engine-backed
                                     # paths only.
    checkpoint_every: Optional[int] = None
                                     # crash-resume (repro.checkpoint):
                                     # snapshot the full RunState every K
                                     # completed rounds.  None (default)
                                     # = no checkpointing, bit for bit.
    checkpoint_path: Optional[str] = None
                                     # where the RunState snapshot lands
                                     # (atomic temp+rename; one file pair,
                                     # overwritten each save)
    resume_from: Optional[str] = None
                                     # path of a RunState snapshot to
                                     # restart from; the run continues at
                                     # the snapshot's round + 1 with
                                     # bit-identical history
    population: Optional[int] = None
                                     # population-scale serving
                                     # (repro.population): the registered
                                     # client population this run samples
                                     # cohorts from.  The sim entry points
                                     # take the Population OBJECT and
                                     # record its size here; None = the
                                     # fleet IS the population (default).
    cohort_size: Optional[int] = None
                                     # clients materialized per round in
                                     # population mode (None with
                                     # population set = the whole
                                     # population — the identity
                                     # configuration)

    def __post_init__(self):
        if self.scheme not in ("feddd", "fedavg", "fedcs", "oort"):
            raise ValueError(f"unknown scheme {self.scheme!r}")
        if self.allocator not in ALLOCATORS:
            raise ValueError(f"unknown allocator {self.allocator!r}; "
                             f"expected one of {ALLOCATORS}")
        if self.rounds_per_dispatch < 1:
            raise ValueError("rounds_per_dispatch must be >= 1, got "
                             f"{self.rounds_per_dispatch}")
        if self.rounds_per_dispatch > 1 and self.allocator != "jax":
            raise ValueError(
                "rounds_per_dispatch > 1 scans the dropout-rate allocation "
                "inside the device step and therefore requires "
                "allocator='jax' (the numpy LP cannot be traced)")
        if self.comm.overhead_aware_allocation and self.allocator != "numpy":
            raise ValueError(
                "comm.overhead_aware_allocation is a host-side fixed point "
                "around the numpy LP; it requires allocator='numpy' (and "
                "therefore cannot ride rounds_per_dispatch > 1)")
        if self.mesh is not None and self.rounds_per_dispatch > 1:
            raise ValueError(
                "mesh (client-sharded SPMD) and rounds_per_dispatch > 1 "
                "are mutually exclusive: the multi-round lax.scan carries "
                "single-device state")
        if self.mesh_collective not in ("dense", "sparse"):
            raise ValueError(f"mesh_collective must be 'dense' or "
                             f"'sparse', got {self.mesh_collective!r}")
        if not 0.0 < self.mesh_keep_fraction <= 1.0:
            raise ValueError(f"mesh_keep_fraction must be in (0,1], got "
                             f"{self.mesh_keep_fraction}")
        aggregation.parse_robust_agg(self.robust_agg)  # validate the spec
        if self.checkpoint_every is not None:
            if self.checkpoint_every < 1:
                raise ValueError("checkpoint_every must be >= 1 (or None "
                                 f"to disable), got {self.checkpoint_every}")
            if not self.checkpoint_path:
                raise ValueError("checkpoint_every requires "
                                 "checkpoint_path: somewhere for the "
                                 "RunState snapshot to land")
        if ((self.checkpoint_every is not None or self.resume_from)
                and self.rounds_per_dispatch > 1):
            raise ValueError(
                "checkpointing / resume operates at per-round dispatch "
                "boundaries; rounds_per_dispatch > 1 keeps rounds on the "
                "device inside one lax.scan and has no boundary to "
                "snapshot at")
        if self.cohort_size is not None and self.population is None:
            raise ValueError("cohort_size requires population= (the "
                             "fleet IS the cohort otherwise)")
        if self.population is not None:
            if self.population < 1:
                raise ValueError(f"population must be >= 1, got "
                                 f"{self.population}")
            k = self.cohort_size
            if k is not None and not 1 <= k <= self.population:
                raise ValueError(f"cohort_size {k} outside [1, "
                                 f"{self.population}]")


@dataclasses.dataclass
class ClientState:
    params: Params                   # W_n^t
    telemetry_idx: int               # row into the telemetry arrays
    num_samples: int
    mask: Optional[Params] = None    # M_n^t of the previous upload


@dataclasses.dataclass
class RoundRecord:
    """One round of history.  Two distinct time axes — do not conflate:

    * ``sim_time`` / ``sim_round_time`` are SIMULATED seconds, the paper's
      Eq. (12) clock: what the federated round *would* take on the modelled
      client links/CPUs.  Time-to-accuracy (Fig. 7) is measured on this
      axis.
    * ``host_wall_time`` is REAL seconds the host process spent computing
      the round (training + engine step) — a throughput measure of this
      implementation, never comparable to ``sim_time``.
    """

    round: int
    sim_time: float                  # cumulative simulated secs (Eq. 12)
    host_wall_time: float            # real host secs spent in this round
    mean_loss: float
    dropout_rates: np.ndarray        # rates allocated for the NEXT round
    uploaded_fraction: float         # raw kept bytes / full bytes
    participants: int
    sim_round_time: float = 0.0      # this round's simulated duration
    uploaded_bytes: float = 0.0      # raw kept-parameter mass (density x U)
    wire_bytes: float = 0.0          # actual on-wire uplink bytes: values
                                     # at the codec's precision + measured
                                     # mask/scale overhead (repro.comm).
                                     # == uploaded_bytes with the default
                                     # CommConfig, bit for bit.
    epsilon: Optional[float] = None
    metrics: Optional[Dict] = None
    # --- failure-model fields (repro.sim.faults); the defaults describe
    # a fault-free round, so pre-fault histories are unchanged.
    survivors: int = -1              # clients alive on the round clock
                                     # (scheduled minus crashed; -1 when
                                     # the driver does not track it)
    retries: int = 0                 # uplink chunk retransmits this round
    abandoned_bytes: float = 0.0     # wire bytes sent but never used:
                                     # crashed/aborted/cut transfers,
                                     # quorum-discarded arrivals
    quarantined_bytes: float = 0.0   # wire bytes of arrivals the payload
                                     # validation screened out of Eq. (4)
    skipped: bool = False            # quorum miss: global held, no step


@dataclasses.dataclass
class RunResult:
    history: List[RoundRecord]
    global_params: Params

    def time_to_accuracy(self, target: float, key: str = "accuracy"
                         ) -> Optional[float]:
        for rec in self.history:
            if rec.metrics and rec.metrics.get(key, -1.0) >= target:
                return rec.sim_time
        return None


def _tree_bytes(params) -> int:
    return sum(l.size * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(params))


class _RoundData(NamedTuple):
    """What one executed round reports back to the shared driver loop."""

    losses: np.ndarray               # server-side loss view after the round
    uploaded_bytes: float            # raw kept bytes uploaded this round
    active: np.ndarray               # (N,) bool: clients on the Eq. (12) clock
    epsilon: Optional[float]         # Assumption-3 estimate (loop only)
    wire_bytes: float                # on-wire bytes (== uploaded_bytes for
                                     # the default CommConfig)


class _RoundExecutor:
    """One round-execution strategy.

    The server's :meth:`FedDDServer.run` owns everything scheme-agnostic —
    the RNG schedule, the allocation LP, the Eq. (12) clock, and history —
    and delegates the round's device math (training dispatch, masks,
    aggregation, client updates) to one of these.  All strategies implement
    the identical Algorithm-1 maths; the engine-backed ones are pinned
    bit-identical (feddd) / float-close (baselines) to the reference loop.
    """

    def __init__(self, server: "FedDDServer", local_train_fn,
                 batched_train_fn):
        self.srv = server
        self.local_train_fn = local_train_fn
        self.batched_train_fn = batched_train_fn

    def run_round(self, t: int, rk: jax.Array, losses: np.ndarray,
                  d_used: np.ndarray) -> _RoundData:
        raise NotImplementedError

    def finalize(self) -> None:
        """Sync any executor-held client state back into server.clients."""

    # -- crash-resume hooks (repro.checkpoint) ------------------------------

    def snapshot_arrays(self):
        """The executor-held client state as a checkpointable pytree."""
        raise NotImplementedError(
            "checkpointing / resume supports the batched-engine and "
            "reference-loop executors; grouped and sharded runs hold "
            "per-group / per-shard device state this snapshot does not "
            "capture yet")

    def restore_arrays(self, arrays) -> None:
        raise NotImplementedError


class _EngineExecutor(_RoundExecutor):
    """Homogeneous fleets: one BatchedRoundEngine jit step per round.

    Client state stays STACKED across rounds (lazy device slices feed the
    per-client python trainer; nothing re-stacks the old params) and syncs
    back into ``server.clients`` on :meth:`finalize`.  Baselines run in
    ``dense_masks`` mode with non-participation as a 0 aggregation weight.
    With ``batched_train_fn`` local training fuses into the device side too;
    for baselines the vmapped trainer runs every row, so non-participants'
    results are masked back to their stale params/losses — reported losses
    and the aggregate reflect actual participation.
    """

    def __init__(self, server, local_train_fn, batched_train_fn):
        super().__init__(server, local_train_fn, batched_train_fn)
        self.engine = round_engine.BatchedRoundEngine(
            server.cfg.selection, server.cfg.comm,
            robust_agg=server.cfg.robust_agg)
        self.weights = np.asarray(
            [cs.num_samples for cs in server.clients], float)
        self.stacked = round_engine.stack_pytrees(
            [cs.params for cs in server.clients])

    def run_round(self, t, rk, losses, d_used) -> _RoundData:
        srv, cfg = self.srv, self.srv.cfg
        obs = srv.obs
        n = srv.tel.num_clients
        dense = cfg.scheme != "feddd"
        part = (np.ones(n, bool) if not dense
                else srv._participants(losses))
        with obs.span("local_train", round=t):
            if self.batched_train_fn is not None:
                stacked_new, loss_dev = self.batched_train_fn(self.stacked,
                                                              rk)
                if dense:
                    # Non-participants must not train this round: keep
                    # their stale params out of the aggregate and their
                    # stale losses in the server's view (the vmapped
                    # trainer computed every row; participation masks the
                    # results).
                    pvec = jnp.asarray(part)
                    stacked_new = jax.tree_util.tree_map(
                        lambda new, old: jnp.where(
                            pvec.reshape((-1,) + (1,) * (new.ndim - 1)),
                            new, old),
                        stacked_new, self.stacked)
                    loss_dev = jnp.where(pvec, jnp.asarray(loss_dev),
                                         jnp.asarray(losses))
            else:
                # baseline non-participants keep their stale state
                loss_dev: List = [None] * n
                new_list = round_engine.train_clients(
                    self.stacked, range(n), self.local_train_fn, rk, part,
                    losses, loss_dev, obs)
                with obs.span("group_stack"):
                    stacked_new = round_engine.stack_pytrees(new_list)
        with obs.span("engine_step", round=t):
            out = self.engine.step(self.stacked, stacked_new,
                                   srv.global_params, d_used,
                                   self.weights * part, rk,
                                   full_round=(t % cfg.h == 0) or dense,
                                   dense_masks=dense)
        srv.global_params = out.global_params
        self.stacked = out.client_params
        # the ONE device->host transfer of the round (wire_overhead is
        # None with the default comm config — no extra sync either way)
        with obs.span("host_transfer", round=t):
            dens, oh, loss_host = jax.device_get(
                (out.densities, out.wire_overhead, loss_dev))
        new_losses = np.asarray(loss_host, float)
        uploaded, wire = account_uplink(dens, part, srv.tel.model_bytes,
                                        oh, cfg.comm, obs=obs)
        return _RoundData(new_losses, uploaded, part, None, wire)

    def finalize(self) -> None:
        n = self.srv.tel.num_clients
        for cs, p in zip(self.srv.clients,
                         round_engine.unstack_pytree(self.stacked, n)):
            cs.params = p

    def snapshot_arrays(self):
        return {"stacked": self.stacked}

    def restore_arrays(self, arrays) -> None:
        self.stacked = jax.tree_util.tree_map(jnp.asarray,
                                              arrays["stacked"])

    # -- multi-round scanned dispatch (rounds_per_dispatch > 1) -------------

    def run_chunk(self, t_start: int, count: int,
                  losses: np.ndarray) -> round_engine.ScanTrace:
        """Run rounds ``t_start .. t_start+count-1`` as ONE lax.scan
        dispatch (:meth:`BatchedRoundEngine.run`), rebinding the stacked
        client state / global params / PRNG key from the final carry and
        returning the host-fetched :class:`ScanTrace` — the chunk's single
        device->host transfer.  The scanned carry donates BOTH model
        buffers (stacked client params and the global params — in-place
        updates where the backend supports donation); the user-provided
        global pytree is copied once before the first chunk so donation
        never invalidates caller-held arrays.
        """
        srv, cfg = self.srv, self.srv.cfg
        if not hasattr(self, "_scan_static"):
            # own the global params before the first donating dispatch:
            # the executor's carry must not alias the caller's pytree
            srv.global_params = jax.tree_util.tree_map(
                lambda x: jnp.array(x, copy=True), srv.global_params)
            # static per run: the staged telemetry, the loss-independent
            # fedcs selection, and oort's system penalty / byte budget
            static_part, pen, budget = None, None, 0.0
            if cfg.scheme == "fedcs":
                static_part = baselines.select_fedcs(srv.tel,
                                                     a_server=cfg.a_server)
            elif cfg.scheme == "oort":
                pen = baselines.oort_system_penalty(srv.tel)
                budget = cfg.a_server * float(np.sum(srv.tel.model_bytes))
            self._scan_static = (
                round_engine.ScanTelemetry.from_host(srv.tel),
                static_part, pen, budget)
        scan_tel, static_part, pen, budget = self._scan_static
        state = round_engine.ScanState(
            client_params=self.stacked,
            global_params=srv.global_params,
            losses=jnp.asarray(losses, jnp.float32),
            dropout=jnp.asarray(srv.dropout, jnp.float32),
            rng=srv.rng,
            sim_time=jnp.zeros((), jnp.float32))
        out, trace = self.engine.run(
            state, scan_tel, num_rounds=count,
            batched_train_fn=self.batched_train_fn, weights=self.weights,
            h=cfg.h, a_server=cfg.a_server, d_max=cfg.d_max,
            delta=cfg.delta,
            global_model_bytes=_tree_bytes(srv.global_params),
            t_start=t_start, scheme=cfg.scheme,
            static_participants=static_part, oort_penalty=pen,
            oort_budget=budget)
        self.stacked = out.client_params
        srv.global_params = out.global_params
        srv.rng = out.rng
        with srv.obs.span("host_transfer", round=t_start):
            return jax.device_get(trace)


class _ShardedEngineExecutor(_EngineExecutor):
    """Homogeneous fleets over a 1-D ``clients`` device mesh: one
    ShardedRoundEngine ``shard_map`` step per round.

    Identical driver flow to :class:`_EngineExecutor` (it inherits
    ``run_round``); only the engine changes — each device owns N/P client
    rows, and the Eq. (4) reduction is the single cross-device exchange
    (dense psum, or the compacted top-K collective of
    core/sparse_collective.py).  The persistent stacked state is placed on
    its shards once, so per-round dispatches never re-shard host arrays;
    with ``batched_train_fn`` the jitted trainer picks the sharding up
    from its inputs and trains shard-local too (GSPMD propagation).
    """

    def __init__(self, server, local_train_fn, batched_train_fn):
        super().__init__(server, local_train_fn, batched_train_fn)
        from repro.launch.mesh import resolve_client_mesh  # launch -> core
        cfg = server.cfg
        mesh = resolve_client_mesh(cfg.mesh)
        self.engine = round_engine.ShardedRoundEngine(
            cfg.selection, cfg.comm, mesh=mesh,
            collective=cfg.mesh_collective,
            keep_fraction=cfg.mesh_keep_fraction,
            robust_agg=cfg.robust_agg)
        n = server.tel.num_clients
        if n % self.engine.num_shards == 0:
            self.stacked = jax.device_put(self.stacked,
                                          self.engine.shard_spec())
        self._spec = WireSpec.from_params(server.global_params,
                                          cfg.selection.channel_axis)

    def run_round(self, t, rk, losses, d_used):
        data = super().run_round(t, rk, losses, d_used)
        # cross-device Eq. (4) bytes: the analytic model of this round's
        # one collective, through the shared accounting hook (host-side
        # arithmetic only — no extra device syncs)
        account_collective(
            self._spec, self.engine.num_shards,
            mode=self.srv.cfg.mesh_collective,
            k_fraction=self.srv.cfg.mesh_keep_fraction, obs=self.srv.obs)
        return data

    def run_chunk(self, t_start, count, losses):
        raise ValueError("rounds_per_dispatch > 1 does not shard "
                         "(ProtocolConfig rejects the combination)")

    def snapshot_arrays(self):
        # placed-on-mesh state would need re-sharding on restore; fall
        # back to the base "unsupported" signal
        return _RoundExecutor.snapshot_arrays(self)


class _GroupedEngineExecutor(_RoundExecutor):
    """Ragged fleets: one GroupedRoundEngine jit step per round.

    Clients are partitioned by sub-model shape (repro.fl.heterogeneity
    .group_by_shape); each group's state stays stacked across rounds.
    Coverage pytrees are computed once per group (members share widths, so
    they share the CR slice) and the per-client mask keys fold the members'
    FLEET positions — grouped rounds are bit-identical to the per-client
    reference loop (tests/test_grouped_engine.py).
    """

    def __init__(self, server, local_train_fn, batched_train_fn):
        super().__init__(server, local_train_fn, batched_train_fn)
        from repro.fl.heterogeneity import group_by_shape  # fl -> core dep
        cfg = server.cfg
        self.weights = np.asarray(
            [cs.num_samples for cs in server.clients], float)
        client_params = [cs.params for cs in server.clients]
        groups = group_by_shape(client_params)
        coverage = [
            cov_mod.coverage_pytree(client_params[g.indices[0]],
                                    server.cr, cfg.selection.channel_axis)
            for g in groups
        ]
        mesh = None
        if cfg.mesh is not None:
            from repro.launch.mesh import resolve_client_mesh
            if cfg.mesh_collective != "dense":
                raise ValueError(
                    "sparse cross-device compaction rides the homogeneous "
                    "sharded engine; ragged (grouped) fleets reduce with "
                    "the dense psum collective")
            mesh = resolve_client_mesh(cfg.mesh)
        self.fleet = round_engine.GroupedFleetState(
            groups, coverage, client_params, cfg.selection,
            server.tel.num_clients, cfg.comm, mesh=mesh,
            robust_agg=cfg.robust_agg)

    def run_round(self, t, rk, losses, d_used) -> _RoundData:
        srv, cfg = self.srv, self.srv.cfg
        obs = srv.obs
        n = srv.tel.num_clients
        dense = cfg.scheme != "feddd"
        part = (np.ones(n, bool) if not dense
                else srv._participants(losses))
        with obs.span("local_train", round=t):
            loss_dev = self.fleet.train(self.local_train_fn, rk, part,
                                        losses, d_used, dense=dense,
                                        obs=obs)
        with obs.span("engine_step", round=t):
            srv.global_params, densities, wire_oh = self.fleet.step(
                srv.global_params, self.weights * part, rk,
                full_round=(t % cfg.h == 0) or dense, dense=dense)
        with obs.span("host_transfer", round=t):
            dens, oh, loss_host = jax.device_get(
                (densities, wire_oh, loss_dev))
        new_losses = np.asarray(loss_host, float)
        uploaded, wire = account_uplink(dens, part, srv.tel.model_bytes,
                                        oh, cfg.comm, obs=obs)
        return _RoundData(new_losses, uploaded, part, None, wire)

    def finalize(self) -> None:
        for cs, p in zip(self.srv.clients, self.fleet.export()):
            cs.params = p


class _ReferenceLoopExecutor(_RoundExecutor):
    """The per-client Python loop — Algorithm 1 verbatim.

    Kept as the bit-exactness oracle for both engines, and as the only
    path producing the per-client mask pytrees ``track_epsilon`` needs.
    Slow by design: per-client build_masks dispatches, per-leaf ``float``
    host syncs, list-based padding and aggregation.
    """

    def snapshot_arrays(self):
        return {"clients": [cs.params for cs in self.srv.clients]}

    def restore_arrays(self, arrays) -> None:
        for cs, p in zip(self.srv.clients, arrays["clients"]):
            cs.params = jax.tree_util.tree_map(jnp.asarray, p)

    def run_round(self, t, rk, losses, d_used) -> _RoundData:
        srv, cfg = self.srv, self.srv.cfg
        obs = srv.obs
        n = srv.tel.num_clients
        losses = losses.copy()
        part = srv._participants(losses)
        eps_val = None

        # --- Step 1: local training (participants only for baselines;
        # in FedDD everyone trains — that is the paper's key point).
        new_params: List[Params] = [None] * n
        with obs.span("local_train", round=t):
            for i, cs in enumerate(srv.clients):
                if cfg.scheme == "feddd" or part[i]:
                    p, l = self.local_train_fn(cs.params, i,
                                               jax.random.fold_in(rk, i))
                    new_params[i] = p
                    losses[i] = float(l)

        # --- Steps 2-3: mask building + (simulated) upload.  Per-client
        # densities / wire overheads collect into vectors so the byte
        # accounting below runs through the ONE shared reduction
        # (repro.comm.payload.account_uplink) every executor uses.
        densities = np.zeros(n)
        wire_oh = (None if cfg.comm.is_default else np.zeros(n))
        client_masks: List[Params] = [None] * n
        with obs.span("encode", round=t):
            if cfg.scheme == "feddd":
                for i, cs in enumerate(srv.clients):
                    cov = (cov_mod.coverage_pytree(
                               cs.params, srv.cr,
                               cfg.selection.channel_axis)
                           if srv.heterogeneous else None)
                    m = selection.build_masks(
                        cs.params, new_params[i],
                        jnp.asarray(d_used[i], jnp.float32),
                        config=cfg.selection, coverage=cov,
                        rng=jax.random.fold_in(rk, 10_000 + i))
                    client_masks[i] = m
                    densities[i] = float(
                        selection.mask_density(new_params[i], m))
            else:
                for i in range(n):
                    if part[i]:
                        client_masks[i] = jax.tree_util.tree_map(
                            lambda w: jnp.ones((1,) * w.ndim, w.dtype),
                            new_params[i])
                        densities[i] = 1.0
            uploads = np.asarray([m is not None for m in client_masks])
            if wire_oh is not None:
                for i in np.flatnonzero(uploads):
                    # baseline full uploads carry collapsed all-ones
                    # masks; their overhead is the closed-form full-upload
                    # constant at true widths (the engines charge the
                    # same)
                    wire_oh[i] = (
                        wire_codecs.mask_overhead_bytes(
                            client_masks[i], new_params[i], cfg.comm)
                        if cfg.scheme == "feddd" else
                        wire_codecs.full_upload_overhead_bytes(
                            srv.wire_specs[i], cfg.comm))

        # --- Step 4: aggregation (over uploaded clients only).  The
        # server aggregates what it DECODED: with qbits < 32 the uploads
        # are quantize->dequantized per client (same PRNG fold as the
        # engines — repro.comm.quantize); Eq. (5)/(6) below keep each
        # client's own full-precision params.
        idxs = [i for i in range(n) if client_masks[i] is not None]
        with obs.span("aggregate", round=t):
            agg_src = {
                i: (new_params[i] if cfg.comm.qbits == 32 else
                    wire_quant.quantize_dequantize(
                        new_params[i], wire_quant.client_quant_key(rk, i),
                        cfg.comm.qbits))
                for i in idxs
            }
            agg_params = [srv._pad_to_global(agg_src[i], i) for i in idxs]
            agg_masks = [srv._pad_mask_to_global(client_masks[i],
                                                 new_params[i])
                         for i in idxs]
            agg_weights = [srv.clients[i].num_samples for i in idxs]
            if cfg.track_epsilon:
                eps_val = float(estimate_epsilon(agg_params, agg_masks))
            srv.global_params = aggregation.aggregate_sparse(
                agg_params, agg_masks, agg_weights,
                prev_global=srv.global_params)

        # --- Steps 6-7: download + local model update
        full_round = (t % cfg.h == 0) or cfg.scheme != "feddd"
        with obs.span("client_update", round=t):
            for i, cs in enumerate(srv.clients):
                if new_params[i] is None:  # non-participant (baselines)
                    if full_round:
                        cs.params = srv._slice_to_local(cs.params)
                    continue
                if full_round or client_masks[i] is None:
                    cs.params = srv._slice_to_local(new_params[i],
                                                    use_global=True)
                else:
                    g_local = srv._slice_like(srv.global_params,
                                              new_params[i])
                    cs.params = aggregation.client_update_sparse(
                        g_local, new_params[i], client_masks[i])

        uploaded, wire = account_uplink(densities, uploads,
                                        srv.tel.model_bytes, wire_oh,
                                        cfg.comm, obs=obs)
        active = (np.ones(n, bool) if cfg.scheme == "feddd" else part)
        return _RoundData(losses, uploaded, active, eps_val, wire)


class FedDDServer:
    """Parameter server for FedDD + the three baselines."""

    def __init__(self, global_params: Params, cfg: ProtocolConfig,
                 telemetry: ClientTelemetry,
                 client_params: Optional[Sequence[Params]] = None):
        self.cfg = cfg
        self.global_params = global_params
        self.tel = telemetry
        n = telemetry.num_clients
        # heterogeneous models: clients may hold pruned sub-models
        if client_params is None:
            client_params = [global_params] * n
        self.clients = [
            ClientState(params=jax.tree_util.tree_map(jnp.asarray, p),
                        telemetry_idx=i,
                        num_samples=int(telemetry.num_samples[i]))
            for i, p in enumerate(client_params)
        ]
        full_w = cov_mod.channel_widths(global_params,
                                        cfg.selection.channel_axis)
        cw = [cov_mod.channel_widths(p, cfg.selection.channel_axis)
              for p in client_params]
        self.cr = cov_mod.coverage_rates(cw, full_w)
        self.heterogeneous = any(w != full_w for w in cw)
        # static per-client wire-format shape specs (repro.comm): the
        # analytic byte model behind the Eq. (12) uplink charge and the
        # overhead-aware allocation
        self.wire_specs = [
            WireSpec.from_params(p, cfg.selection.channel_axis)
            for p in client_params
        ]
        self.dropout = np.zeros(n)           # D_n^1 = 0 (Algorithm 1)
        self.rng = jax.random.PRNGKey(cfg.seed)
        # observability hook: inert singleton until run() builds a live
        # recorder for an active cfg.obs (repro.obs)
        self.obs = obs_mod.NULL_RECORDER

    # -- per-round server logic ---------------------------------------------

    def allocate(self, losses: np.ndarray) -> AllocationResult:
        tel = dataclasses.replace(self.tel, train_loss=losses)
        if self.cfg.comm.overhead_aware_allocation:
            return solve_dropout_rates_overhead_aware(
                tel, self.wire_specs, comm=self.cfg.comm,
                a_server=self.cfg.a_server, d_max=self.cfg.d_max,
                delta=self.cfg.delta,
                global_model_bytes=_tree_bytes(self.global_params))
        return solve_dropout_rates_with(
            self.cfg.allocator, tel,
            a_server=self.cfg.a_server, d_max=self.cfg.d_max,
            delta=self.cfg.delta,
            global_model_bytes=_tree_bytes(self.global_params))

    def _participants(self, losses: np.ndarray) -> np.ndarray:
        if self.cfg.scheme == "fedavg":
            return baselines.select_fedavg(self.tel)
        if self.cfg.scheme == "fedcs":
            return baselines.select_fedcs(self.tel,
                                          a_server=self.cfg.a_server)
        if self.cfg.scheme == "oort":
            tel = dataclasses.replace(self.tel, train_loss=losses)
            return baselines.select_oort(tel, a_server=self.cfg.a_server)
        return np.ones(self.tel.num_clients, bool)   # feddd: everyone

    # -- executor routing -----------------------------------------------------

    def _executor_kind(self, batched_train_fn) -> str:
        """Route a run to its executor (see the module routing table).

        ``track_epsilon`` needs the reference loop's per-client mask
        pytrees; ``batched=False`` forces the loop as the oracle.  A
        homogeneous engine run may fuse training via ``batched_train_fn``
        (any scheme — baselines mask non-participants); the grouped and
        loop paths cannot accept it (client data shards are ragged /
        per-client by construction).
        """
        if self.cfg.track_epsilon or not self.cfg.batched:
            kind = "loop"
        elif self.heterogeneous:
            kind = "grouped"
        else:
            kind = "engine"
        if self.cfg.mesh is not None:
            if kind == "loop":
                raise ValueError(
                    "mesh (client-sharded SPMD) requires engine-backed "
                    "execution; track_epsilon / batched=False route to "
                    "the per-client reference loop, which does not shard")
            if kind == "engine":
                kind = "sharded"
            # grouped: the GroupedRoundEngine itself shards each group's
            # member axis when cfg.mesh is set (see _GroupedEngineExecutor)
        if batched_train_fn is not None and kind not in ("engine",
                                                         "sharded"):
            raise ValueError(
                "batched_train_fn requires a homogeneous run with "
                "batched=True and track_epsilon=False")
        if str(self.cfg.robust_agg) != "mean" and kind == "loop":
            raise ValueError(
                "robust_agg variants are fused into the engine-backed "
                "stacked Eq. (4) step; the reference loop aggregates "
                "per-client lists with the plain weighted mean (run with "
                "batched=True and track_epsilon=False)")
        return kind

    _EXECUTORS = {"engine": _EngineExecutor,
                  "sharded": _ShardedEngineExecutor,
                  "grouped": _GroupedEngineExecutor,
                  "loop": _ReferenceLoopExecutor}

    @property
    def executor_kind(self) -> str:
        """The executor a plain ``run(local_train_fn)`` will route to —
        "engine" (homogeneous batched), "grouped" (ragged fleet), or
        "loop" (the per-client reference)."""
        return self._executor_kind(None)

    # -- the full run ---------------------------------------------------------

    def run(self,
            local_train_fn: Optional[Callable[[Params, int, jax.Array],
                                              "tuple[Params, float]"]] = None,
            eval_fn: Optional[Callable[[Params], Dict]] = None,
            rounds: Optional[int] = None,
            batched_train_fn: Optional[Callable] = None) -> RunResult:
        """Run the protocol.

        Args:
          local_train_fn: per-client ``(params, client_idx, rng) ->
            (params, loss)`` — required unless ``batched_train_fn`` given.
          batched_train_fn: optional ``(stacked_params, rng) ->
            (stacked_params, (N,) losses)`` operating on client-STACKED
            pytrees; when provided (homogeneous engine runs only) local
            training fuses into the device-side round and client state
            stays stacked across rounds.
        """
        cfg = self.cfg
        rounds = rounds or cfg.rounds
        n = self.tel.num_clients
        if local_train_fn is None and batched_train_fn is None:
            raise ValueError("need local_train_fn or batched_train_fn")
        losses = np.ones(n)
        sim_time = 0.0
        history: List[RoundRecord] = []
        full_bytes = float(np.sum(self.tel.model_bytes))

        kind = self._executor_kind(batched_train_fn)
        if cfg.rounds_per_dispatch > 1:
            if kind != "engine":
                raise ValueError(
                    "rounds_per_dispatch > 1 requires the homogeneous "
                    "batched engine (batched=True, track_epsilon=False, "
                    f"homogeneous fleet); this run routes to {kind!r}")
            if batched_train_fn is None:
                raise ValueError(
                    "rounds_per_dispatch > 1 requires batched_train_fn: "
                    "local training must be device-fused for the round "
                    "loop to scan")
            if eval_fn is not None:
                raise ValueError(
                    "eval_fn evaluates every round on the host, but with "
                    "rounds_per_dispatch > 1 params only reach the host "
                    "at dispatch boundaries; use rounds_per_dispatch=1 "
                    "for per-round eval")

        self.obs = obs_mod.make_recorder(
            cfg.obs, driver="protocol", scheme=cfg.scheme, executor=kind
            if cfg.rounds_per_dispatch == 1 else "scanned",
            clients=n, rounds=rounds)
        try:
            with self.obs.span("fleet_stack"):
                executor = self._EXECUTORS[kind](self, local_train_fn,
                                                 batched_train_fn)

            # --- crash-resume (repro.checkpoint): restore a snapshot
            # before the loop, save one every checkpoint_every completed
            # rounds.  checkpoint_every=None and resume_from=None touch
            # nothing.
            start_t = 1
            if cfg.resume_from:
                from repro import checkpoint as ckpt_mod  # checkpoint -> core
                st = ckpt_mod.load_run_state(
                    cfg.resume_from, self._snapshot_arrays(executor, losses))
                losses = self._restore_arrays(executor, st.arrays)
                history = st.history
                sim_time = float(st.extra.get("sim_time", 0.0))
                start_t = st.round + 1

            if cfg.rounds_per_dispatch > 1:
                self._run_scanned(executor, rounds, history, full_bytes)
                with self.obs.span("fleet_unstack"):
                    executor.finalize()
                return RunResult(history, self.global_params)

            for t in range(start_t, rounds + 1):
                t0 = time.perf_counter()
                self.rng, rk = jax.random.split(self.rng)
                d_used = self.dropout.copy()  # D_t: what uploads use

                rd = executor.run_round(t, rk, losses, d_used)
                losses = rd.losses

                # --- Step 5: dropout-rate allocation for round t+1
                if cfg.scheme == "feddd":
                    with self.obs.span("allocate", round=t):
                        alloc = self.allocate(np.maximum(losses, 1e-6))
                    self.dropout = alloc.dropout_rates

                metrics = None
                if eval_fn:
                    with self.obs.span("eval", round=t):
                        metrics = eval_fn(self.global_params)

                with self.obs.span("round_records", round=t):
                    # --- simulated wall clock (paper Eq. (12))
                    sim_time, round_t, t_all = self._clock(
                        rd.active, sim_time, d_used)
                    history.append(self._record(t, t0, sim_time, round_t,
                                                losses, rd.uploaded_bytes,
                                                rd.wire_bytes, full_bytes,
                                                rd.active, rd.epsilon,
                                                metrics))
                    if self.obs.active:
                        self.obs.round(
                            history[-1], path=kind, scheme=cfg.scheme,
                            client_times=np.where(rd.active, t_all, np.nan))
                    if (cfg.checkpoint_every is not None
                            and t % cfg.checkpoint_every == 0):
                        from repro import checkpoint as ckpt_mod
                        ckpt_mod.save_run_state(
                            cfg.checkpoint_path,
                            ckpt_mod.RunState(
                                round=t,
                                arrays=self._snapshot_arrays(executor,
                                                             losses),
                                history=history,
                                extra={"sim_time": sim_time}))

            with self.obs.span("fleet_unstack"):
                executor.finalize()
            return RunResult(history, self.global_params)
        finally:
            self.obs.close()
            self.obs = obs_mod.NULL_RECORDER

    # -- crash-resume snapshot plumbing (repro.checkpoint) -------------------

    def _snapshot_arrays(self, executor: _RoundExecutor,
                         losses: np.ndarray) -> Dict:
        """Everything round t+1 reads, as one checkpointable pytree.

        The executor contributes the client state it holds; the server
        adds the global params, the protocol PRNG key (split stream —
        uint32, persisted exactly), the loss view, and the allocated
        dropout rates D_{t+1}.  Fault/outage/network draws are keyed per
        epoch and replay free (see repro.checkpoint.run_state).
        """
        return {"executor": executor.snapshot_arrays(),
                "global": self.global_params,
                "rng": self.rng,
                "losses": np.asarray(losses, np.float64),
                "dropout": np.asarray(self.dropout, np.float64)}

    def _restore_arrays(self, executor: _RoundExecutor,
                        arrays: Dict) -> np.ndarray:
        """Inverse of :meth:`_snapshot_arrays`; returns the loss view."""
        executor.restore_arrays(arrays["executor"])
        self.global_params = jax.tree_util.tree_map(jnp.asarray,
                                                    arrays["global"])
        self.rng = jnp.asarray(arrays["rng"])
        self.dropout = np.asarray(arrays["dropout"], np.float64)
        return np.asarray(arrays["losses"], np.float64)

    def _run_scanned(self, executor: "_EngineExecutor", rounds: int,
                     history: List[RoundRecord], full_bytes: float) -> None:
        """Chunked multi-round execution: ``rounds_per_dispatch`` rounds
        per ``lax.scan`` device dispatch, spliced back into the per-round
        :class:`RoundRecord` stream.

        The scan carries the f32 device rendering of the round clock; the
        RECORDS recompute allocation clipping and the Eq. (12) clock
        host-side in float64 from the traced rates/participants — exactly
        the sequential driver's arithmetic — so a scanned history matches
        per-round dispatch bit for bit wherever the in-scan allocator
        does (always for the learning state; rates to f32-ulp scale —
        tests/test_round_engine.py).  ``host_wall_time`` is the chunk
        wall time amortised over its rounds (individual rounds are not
        host-observable by design).
        """
        cfg = self.cfg
        losses = np.ones(self.tel.num_clients)
        sim_time = 0.0
        t = 1
        while t <= rounds:
            k = min(cfg.rounds_per_dispatch, rounds - t + 1)
            t0 = time.perf_counter()
            with self.obs.span("chunk_dispatch", round=t):
                trace = executor.run_chunk(t, k, losses)
            wall = (time.perf_counter() - t0) / k
            # the chunk's records, rebuilt on the host from its ScanTrace
            with self.obs.span("round_records", round=t):
                tr_losses = np.asarray(trace.losses, float)
                tr_dens = np.asarray(trace.densities, float)
                tr_dnext = np.asarray(trace.next_dropout, np.float64)
                tr_part = np.asarray(trace.participants, bool)
                tr_oh = (None if trace.wire_overhead is None
                         else np.asarray(trace.wire_overhead))
                for j in range(k):
                    d_used = self.dropout.copy()
                    part = tr_part[j]
                    losses = tr_losses[j]
                    if cfg.scheme == "feddd":
                        # the sequential driver clips the device rates in
                        # float64 (solve_dropout_rates_with); replay that
                        # on the traced rates so records match bit for bit
                        self.dropout = np.clip(tr_dnext[j], 0.0, cfg.d_max)
                    uploaded, wire = account_uplink(
                        tr_dens[j], part, self.tel.model_bytes,
                        None if tr_oh is None else tr_oh[j], cfg.comm,
                        obs=self.obs)
                    sim_time, round_t, t_all = self._clock(part, sim_time,
                                                           d_used)
                    history.append(RoundRecord(
                        round=t + j, sim_time=sim_time,
                        sim_round_time=round_t, host_wall_time=wall,
                        mean_loss=float(np.mean(losses)),
                        dropout_rates=self.dropout.copy(),
                        uploaded_fraction=uploaded / max(full_bytes, 1e-9),
                        uploaded_bytes=uploaded, wire_bytes=wire,
                        participants=int(np.sum(part)),
                        survivors=int(np.sum(part))))
                    if self.obs.active:
                        self.obs.round(
                            history[-1], path="scanned", scheme=cfg.scheme,
                            client_times=np.where(part, t_all, np.nan))
            t += k

    def _record(self, t: int, t0: float, sim_time: float,
                sim_round_time: float, losses: np.ndarray,
                uploaded_bytes: float, wire_bytes: float, full_bytes: float,
                active: np.ndarray, eps_val: Optional[float],
                metrics: Optional[Dict]) -> RoundRecord:
        return RoundRecord(
            round=t, sim_time=sim_time, sim_round_time=sim_round_time,
            host_wall_time=time.perf_counter() - t0,
            mean_loss=float(np.mean(losses)),
            dropout_rates=self.dropout.copy(),
            uploaded_fraction=uploaded_bytes / max(full_bytes, 1e-9),
            uploaded_bytes=uploaded_bytes, wire_bytes=wire_bytes,
            participants=int(np.sum(active)),
            survivors=int(np.sum(active)),
            epsilon=eps_val, metrics=metrics)

    def _clock(self, active: np.ndarray, sim_time: float,
               dropout_used: np.ndarray
               ) -> "tuple[float, float, np.ndarray]":
        """Simulated wall clock (paper Eq. (12)).

        ``dropout_used`` is D_t — the rates this round's uploads actually
        used (NOT the freshly allocated D_{t+1}; the allocation for the
        next round happens before the clock update).

        With a non-default wire format the UPLINK leg charges the codec's
        analytic byte model (mask overhead + value precision,
        repro.comm.payload.analytic_wire_bytes) instead of the idealized
        ``U(1-D)``; the downlink broadcast stays idealized.

        Returns ``(sim_time, round_time, t_all)``; ``t_all`` holds the
        per-client Eq. (12) round times the max ran over; the recorder
        logs them (masked to active clients) as the straggler timeline.
        """
        d_for_time = (dropout_used if self.cfg.scheme == "feddd"
                      else np.zeros(self.tel.num_clients))
        up = (None if self.cfg.comm.is_default else
              analytic_uplink_vector(self.wire_specs, d_for_time,
                                     self.cfg.comm))
        t_all = baselines.round_times(self.tel, d_for_time,
                                      uplink_bytes=up)
        round_t = float(np.max(t_all[active]))
        return sim_time + round_t, round_t, t_all

    # -- heterogeneous-model plumbing  (HeteroFL-style width slicing) --------

    def _pad_to_global(self, params, client_idx):
        """Zero-pad a client sub-model up to global widths."""
        def _pad(p, g):
            if p.shape == g.shape:
                return p
            pads = [(0, gs - ps) for ps, gs in zip(p.shape, g.shape)]
            return jnp.pad(p, pads)
        return jax.tree_util.tree_map(_pad, params, self.global_params)

    def _pad_mask_to_global(self, masks, params):
        """Masks are channel-shaped; pad with zeros so padded (absent)
        channels never contribute to the aggregate."""
        def _pad(m, p, g):
            m_full = jnp.broadcast_to(m, p.shape)
            if p.shape == g.shape:
                return m_full
            pads = [(0, gs - ps) for ps, gs in zip(p.shape, g.shape)]
            return jnp.pad(m_full, pads)
        return jax.tree_util.tree_map(_pad, masks, params,
                                      self.global_params)

    def _slice_like(self, global_params, local_params):
        return round_engine.slice_pytree(global_params, local_params)

    def _slice_to_local(self, local_params, use_global: bool = True):
        src = self.global_params if use_global else local_params
        return self._slice_like(src, local_params)


def run_scheme(scheme: str, global_params, telemetry, local_train_fn,
               eval_fn=None, client_params=None, *, sim=None, network=None,
               faults=None, population=None, cohort_size=None,
               **cfg_kw) -> RunResult:
    """One-call convenience wrapper used by benchmarks and examples.

    Passing ``sim`` (a :class:`repro.sim.runner.SimConfig`, or ``True``
    for defaults) and/or ``network`` (a :class:`repro.sim.network
    .NetworkModel`) routes the run through the event-driven simulator
    instead of the closed-form Eq. (12) clock: dynamic per-round network
    conditions, observed-telemetry LP re-solves, and sync / deadline /
    async aggregation policies.  ``faults`` (a
    :class:`repro.sim.faults.FaultModel`) additionally injects client
    crashes, lossy uplinks, and corrupted payloads, and enables the
    server's quarantine/quorum degradation (wave policies only; the
    async policy gets crash/loss + staleness-budget semantics, while
    corruption stays wave-only).  Ragged ``client_params`` fleets run
    the grouped engine on either path (see the routing table in the
    module docstring).

    The survivability knobs ride ``**cfg_kw`` onto either path:
    ``robust_agg=`` selects the Byzantine-robust Eq. (4) variant, and
    ``checkpoint_every=`` / ``checkpoint_path=`` / ``resume_from=``
    drive bit-identical crash-resume (repro.checkpoint).

    ``population`` (a :class:`repro.population.Population`) +
    ``cohort_size`` switch to population-scale serving: ``telemetry``
    covers the registered population and each round materializes only a
    sampled cohort (availability churn + samplers live on the Population
    object).  Population runs always route through the simulator.
    """
    if (sim is not None or network is not None or faults is not None
            or population is not None):
        from repro.sim import runner as sim_runner   # local: sim -> core
        if sim is None or sim is True:
            sim = sim_runner.SimConfig()
        return sim_runner.run_sim(scheme, global_params, telemetry,
                                  local_train_fn, eval_fn, sim=sim,
                                  network=network, faults=faults,
                                  client_params=client_params,
                                  population=population,
                                  cohort_size=cohort_size, **cfg_kw)
    cfg = ProtocolConfig(scheme=scheme, **cfg_kw)
    server = FedDDServer(global_params, cfg, telemetry, client_params)
    return server.run(local_train_fn, eval_fn)
