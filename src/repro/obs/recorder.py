"""Run recorder — the one observability hook the drivers talk to.

:class:`ObsConfig` rides :class:`repro.core.protocol.ProtocolConfig`
(field ``obs``); the protocol driver and the sim runner build a recorder
per run via :func:`make_recorder`.  The default config is INERT: it
resolves to the shared :data:`NULL_RECORDER`, whose every method is a
no-op returning immediately — the hard contract is that disabled
observability leaves learning state bit-identical on all four execution
paths and compiles the identical engine programs (tests/test_obs.py pins
both, mirroring the zero-rate-faults contract of repro.sim.faults).

A live :class:`Recorder` composes three sinks:

* a :class:`~repro.obs.metrics.MetricsRegistry` (own or shared via
  ``ObsConfig.registry``) — round/byte/failure counters, per-scheme
  loss/accuracy gauges, span histograms;
* an optional JSONL run log (``ObsConfig.jsonl_path`` —
  repro.obs.runlog), one event per round / span / fault incident;
* optional ``jax.profiler`` trace annotations (``ObsConfig.trace``):
  every host span also enters a ``TraceAnnotation``, so spans line up
  with device activity in a profiler trace.  The fused/scanned device
  pipelines themselves are annotated UNCONDITIONALLY with
  ``jax.named_scope`` phase names (compile-time metadata only — see
  core/round_engine.py), which is why enabling tracing never triggers a
  recompile.

Everything the recorder consumes is already host-side (the round's one
``device_get`` / the chunk's ``ScanTrace`` pull): recording adds no
device->host transfers.

Span vocabulary (:data:`PHASES`; ``protocol`` is ``FedDDServer.run`` and
its executors in core/protocol.py, ``sim`` the wave runner in
sim/runner.py).  Spans nest: each JSONL ``span`` event names the span
that enclosed it as ``parent`` (null at the top level).

==================  ========================================  ===========
name                what it covers                            emitted by
==================  ========================================  ===========
``fleet_stack``     building the executor: stacking client    protocol
                    params, or grouping, coverage and
                    per-group stacking for ragged fleets
``allocate``        the Eq. (9)-(11) dropout-rate LP          both
``local_train``     local training: the fused trainer's       both
                    dispatch, or the per-client loop
``group_unstack``   one group's (or the fleet's) stacked      protocol,
                    params into per-client slices             sim grouped
``client_train``    one client's ``local_train_fn`` call,     protocol,
                    nested in ``local_train``                 sim grouped
``group_stack``     restacking the trained clients (and       protocol,
                    assembling a group's ``GroupBatch``)      sim grouped
``encode``          the reference loop's mask building        protocol
``aggregate``       the reference loop's Eq. (4)              protocol
``client_update``   the reference loop's Eq. (5)/(6)          protocol
``engine_step``     the fused server step's dispatch          both
``host_transfer``   the round's (chunk's) one device_get      both
``chunk_dispatch``  one scanned chunk of rounds               protocol
``transport``       the simulated event timeline              sim
``eval``            the caller's ``eval_fn``                  both
``round_records``   host bookkeeping after a round (Eq. (12)  protocol
                    clock, RoundRecord, run-log events,
                    checkpoint test); one per scanned chunk
``fleet_unstack``   ``finalize``: the stacked state back      protocol
                    into ``server.clients``
==================  ========================================  ===========

Compile counter: while a live recorder is open it listens to
``jax.monitoring`` and counts the programs JAX compiles into
``feddd_compiles_total{kind=compile}`` (every backend compile, a load
from the persistent cache included) and ``{kind=cache_load}`` (the loads
among them), logging a ``compile`` event with the seconds taken and the
round of the innermost open span that names one (null outside rounds:
the fleet's stacking and unstacking, the key split between rounds).
``python -m repro.obs.report`` prints the counts by round.  A fleet
compiles in its first rounds (round 1, and round ``h``, the first full
broadcast); a compile in any later round is a recompile mid-run (new
shapes, new closed-over data, a changed static argument), and a second
``run`` of a warm fleet in the same process should count none.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, Iterator, Optional

import numpy as np

from repro.obs.metrics import MetricsRegistry
from repro.obs.runlog import SCHEMA_VERSION, JsonlWriter, round_event

# The host-span vocabulary (module docstring: what each covers and which
# driver emits it).  The device programs' ``feddd_*`` named scopes in
# core/round_engine.py are a separate, device-side vocabulary.
PHASES = ("fleet_stack", "allocate", "local_train", "group_unstack",
          "client_train", "group_stack", "encode", "aggregate",
          "client_update", "engine_step", "host_transfer", "chunk_dispatch",
          "transport", "eval", "round_records", "fleet_unstack")

# jax.monitoring duration events -> ``feddd_compiles_total`` kinds (the
# benchmark's bench/run.py CompileCounter counts the same two events)
COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """Observability knobs (``ProtocolConfig.obs``).

    enabled: master switch.  Any of the other fields being set also
      activates recording (setting a log path IS opting in).
    jsonl_path: write the structured JSONL run log here (repro.obs.runlog;
      overwritten per run).
    trace: wrap host spans in ``jax.profiler.TraceAnnotation`` so they
      show up in profiler traces next to device activity.
    registry: share a :class:`MetricsRegistry` across runs (benchmark
      sweeps aggregating into one export); None gives the run its own.
    """

    enabled: bool = False
    jsonl_path: Optional[str] = None
    trace: bool = False
    registry: Optional[MetricsRegistry] = None

    @property
    def active(self) -> bool:
        return bool(self.enabled or self.jsonl_path or self.trace
                    or self.registry is not None)


class NullRecorder:
    """Inert recorder — every hook no-ops.  Shared singleton
    :data:`NULL_RECORDER`; the disabled-observability bit-identity
    contract rests on these methods doing nothing at all."""

    active = False
    registry = None

    def span(self, name: str, round: Optional[int] = None):  # noqa: A002
        return contextlib.nullcontext()

    def span_done(self, name: str, t_start: float,
                  round: Optional[int] = None) -> None:  # noqa: A002
        pass

    def event(self, kind: str, /, **fields) -> None:
        pass

    def fault(self, round: int, incident: Dict) -> None:  # noqa: A002
        pass

    def uplink(self, uploaded_bytes: float, wire_bytes: float) -> None:
        pass

    def collective(self, dense_bytes: float, wire_bytes: float) -> None:
        pass

    def round(self, record, *, path: str = "", scheme: str = "",
              client_times=None) -> None:
        pass

    def close(self) -> None:
        pass


NULL_RECORDER = NullRecorder()


def update_round_metrics(reg: MetricsRegistry, record, *, scheme: str,
                         path: str) -> None:
    """Fold one RoundRecord into a registry — THE round->metrics mapping,
    shared by the live recorder and the offline report's ``--prom``
    replay so both render identical numbers."""
    lbl = dict(scheme=scheme, path=path)
    reg.inc("feddd_rounds_total", 1, **lbl)
    if record.skipped:
        reg.inc("feddd_rounds_skipped_total", 1, **lbl)
    if record.retries:
        reg.inc("feddd_retries_total", record.retries, **lbl)
    if record.abandoned_bytes:
        reg.inc("feddd_abandoned_bytes_total", record.abandoned_bytes,
                **lbl)
    if record.quarantined_bytes:
        reg.inc("feddd_quarantined_bytes_total",
                record.quarantined_bytes, **lbl)
    reg.set("feddd_mean_loss", record.mean_loss, scheme=scheme)
    reg.set("feddd_sim_time_seconds", record.sim_time, scheme=scheme)
    if record.metrics and "accuracy" in record.metrics:
        reg.set("feddd_accuracy", float(record.metrics["accuracy"]),
                scheme=scheme)
    reg.observe("feddd_round_host_seconds", record.host_wall_time, **lbl)
    reg.observe("feddd_sim_round_seconds", record.sim_round_time, **lbl)


class Recorder:
    """Live recorder: metrics + spans + JSONL events for one run."""

    active = True

    def __init__(self, cfg: ObsConfig, *, driver: str, **meta):
        self.cfg = cfg
        self.registry = cfg.registry if cfg.registry is not None \
            else MetricsRegistry()
        self._writer = (JsonlWriter(cfg.jsonl_path)
                        if cfg.jsonl_path else None)
        self._t0 = time.perf_counter()
        self._rounds = 0
        self._host_s = 0.0
        self._sim_s = 0.0
        self._closed = False
        self._open: list = []            # names of the spans now open
        # the round of the innermost open span that names one
        self._round: Optional[int] = None
        self.event("run_start", schema=SCHEMA_VERSION, driver=driver,
                   **meta)
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    # -- spans -----------------------------------------------------------

    @contextlib.contextmanager
    def span(self, name: str,
             round: Optional[int] = None) -> Iterator[None]:  # noqa: A002
        """Host-side span around one pipeline phase.  With
        ``ObsConfig.trace`` the span also enters a ``jax.profiler``
        TraceAnnotation, so profiler timelines carry the same names."""
        ctx = contextlib.nullcontext()
        if self.cfg.trace:
            import jax
            ctx = jax.profiler.TraceAnnotation(name)
        prev_round = self._round
        if round is not None:
            self._round = int(round)
        start = time.perf_counter()
        self._open.append(name)
        try:
            with ctx:
                yield
        finally:
            self._open.pop()
            self._round = prev_round
        self.span_done(name, start, round=round)

    def span_done(self, name: str, t_start: float,
                  round: Optional[int] = None) -> None:  # noqa: A002
        """Record a span that already ran, from its ``perf_counter`` start.

        For phases awkward to wrap in a ``with`` block (the sim runner's
        event-timeline section).  No profiler annotation — retroactive
        spans cannot wrap device dispatches.  Its ``parent`` is the span
        open now, if any.
        """
        dur = time.perf_counter() - t_start
        self.registry.observe("feddd_span_seconds", dur, name=name)
        ev = {"name": name, "t_start": t_start - self._t0, "dur_s": dur,
              "parent": self._open[-1] if self._open else None}
        if round is not None:
            ev["round"] = int(round)
        self.event("span", **ev)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        """``jax.monitoring`` listener: count and log compiles (``round``:
        that of the innermost open span naming one, else null)."""
        kind = COMPILE_EVENTS.get(event)
        if kind is None:
            return
        self.registry.inc("feddd_compiles_total", 1, kind=kind)
        self.event("compile", kind=kind, round=self._round,
                   seconds=float(duration))

    # -- events ----------------------------------------------------------

    def event(self, kind: str, /, **fields) -> None:
        # ``kind`` is positional-only: fault incidents legitimately carry
        # a "kind" field of their own (crash/retry/...), which must land
        # in ``fields`` rather than collide with the event kind.
        if self._writer is not None:
            self._writer.write({"event": kind, **fields})

    def fault(self, round: int, incident: Dict) -> None:  # noqa: A002
        """One fault incident (repro.sim.faults.incident_events dict)."""
        self.registry.inc("feddd_fault_incidents_total", 1,
                          kind=incident.get("kind", "unknown"))
        self.event("fault", round=round, **incident)

    def uplink(self, uploaded_bytes: float, wire_bytes: float) -> None:
        """Byte counters fed from THE shared reduction
        (repro.comm.payload.account_uplink)."""
        self.registry.inc("feddd_uploaded_bytes_total",
                          float(uploaded_bytes))
        self.registry.inc("feddd_wire_bytes_total", float(wire_bytes))

    def collective(self, dense_bytes: float, wire_bytes: float) -> None:
        """Cross-device Eq. (4) reduction bytes, fed from THE shared
        reduction (repro.comm.payload.account_collective).  ``dense_bytes``
        is the dense-psum equivalent, ``wire_bytes`` what the configured
        collective actually moved; the ``feddd_cross_device_bytes`` gauge
        tracks the latest round so dashboards see the live (1-D) per-link
        saving next to the cumulative counters."""
        self.registry.inc("feddd_collective_dense_bytes_total",
                          float(dense_bytes))
        self.registry.inc("feddd_collective_bytes_total",
                          float(wire_bytes))
        self.registry.set("feddd_cross_device_bytes", float(wire_bytes))
        self.event("collective", dense=float(dense_bytes),
                   wire=float(wire_bytes))

    def round(self, record, *, path: str = "", scheme: str = "",
              client_times=None) -> None:
        """Fold one finished RoundRecord into metrics + the run log.

        ``client_times`` (optional, (N,) float, NaN = did not upload) are
        the per-client upload-completion offsets on the SIMULATED clock —
        the straggler-timeline axis of ``repro.obs.report``.
        """
        self._rounds += 1
        self._host_s += float(record.host_wall_time)
        self._sim_s = float(record.sim_time)
        update_round_metrics(self.registry, record, scheme=scheme,
                             path=path)
        if self._writer is not None:
            extra = {"path": path, "scheme": scheme}
            if client_times is not None:
                ct = np.asarray(client_times, float)
                extra["client_up"] = [None if not np.isfinite(v)
                                      else float(v) for v in ct]
            self._writer.write(round_event(record, **extra))

    def close(self) -> None:
        """Final run_end event + run-level gauges.  Idempotent."""
        if self._closed:
            return
        self._closed = True
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        wall = time.perf_counter() - self._t0
        rps = self._rounds / wall if wall > 0 else 0.0
        self.registry.set("feddd_rounds_per_sec", rps)
        self.event("run_end", rounds=self._rounds, wall_s=wall,
                   host_round_s=self._host_s, sim_s=self._sim_s,
                   rounds_per_sec=rps)
        if self._writer is not None:
            self._writer.close()


def make_recorder(cfg: Optional[ObsConfig], *, driver: str, **meta):
    """Recorder for an active config, :data:`NULL_RECORDER` otherwise."""
    if cfg is None or not cfg.active:
        return NULL_RECORDER
    return Recorder(cfg, driver=driver, **meta)
