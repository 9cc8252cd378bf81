"""Unified observability layer: metrics registry, round/span tracing,
structured JSONL run logs, and a run-inspection CLI.

Entry points:

* :class:`ObsConfig` — rides ``ProtocolConfig.obs``; default is inert.
* :func:`make_recorder` — a :class:`Recorder` for active configs, the
  shared :data:`NULL_RECORDER` (all no-ops) otherwise.
* :class:`MetricsRegistry` — counters/gauges/histograms with labels,
  Prometheus-text + CSV rendering (also the benchmark export path).
* ``repro.obs.runlog`` — schema-versioned JSONL events; round events
  round-trip to bit-identical RoundRecords.
* ``python -m repro.obs.report <run.jsonl>`` — phase/byte/failure
  summaries, compile counts, straggler timelines, ``--csv`` / ``--prom``
  export.
* :data:`PHASES` — the host-span vocabulary; ``repro.obs.recorder``'s
  docstring says what each span covers and which driver emits it, and
  how the compile counter catches recompiles mid-run.

Import discipline: core/sim modules import ``repro.obs``; nothing in
this package imports core/sim at module level (runlog pulls RoundRecord
lazily), so the dependency edge stays one-way.
"""

from repro.obs.metrics import DEFAULT_BUCKETS, MetricsRegistry
from repro.obs.recorder import (NULL_RECORDER, ObsConfig, NullRecorder,
                                PHASES, Recorder, make_recorder,
                                update_round_metrics)
from repro.obs.runlog import (SCHEMA_VERSION, JsonlWriter,
                              history_from_events, jsonable, load_history,
                              read_events, record_from_event, round_event)

__all__ = [
    "DEFAULT_BUCKETS", "MetricsRegistry",
    "NULL_RECORDER", "ObsConfig", "NullRecorder", "PHASES", "Recorder",
    "make_recorder", "update_round_metrics",
    "SCHEMA_VERSION", "JsonlWriter", "history_from_events", "jsonable",
    "load_history", "read_events", "record_from_event", "round_event",
]
