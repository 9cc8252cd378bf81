"""From a profiler trace to the numbers the per-layer readers take.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote, with
nothing but JAX, into plain records: device operations (device, start,
end, name, scope, program) and host spans (the ``TraceAnnotation`` names
of ``repro.obs`` and the benchmark's own ``WINDOW`` span around the timed
call).  ``reduce`` turns them into busy time, idle gaps named by the host
span open at the time, and device time by round phase.  Both halves are
plain functions of their inputs, so a small fixture checks the arithmetic.

Phases: a device operation belongs to ``feddd_<phase>`` when that
``jax.named_scope`` name appears in its scope (the program's round-engine
annotations), to ``local_train`` when it runs in the caller's jitted
training program (``TRAIN_PROGRAMS``), and to ``other`` otherwise.  The
TPU trace names an operation by its HLO instruction only, so the scope
comes from the compiled programs' own HLO text (``op_name`` metadata),
which :class:`ScopeMap` records as the run loads or compiles them.  The
trace nests the operations of a loop inside the loop's own event; phase
and operation times count the innermost events only.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench_window"
# jitted programs that are local training as a whole: the fused trainer of
# ``make_batched_train_fn`` and the per-client SGD step of
# ``make_local_train_fn``
TRAIN_PROGRAMS = ("jit_batched", "jit__step")
HOST_SPANS = ("allocate", "local_train", "engine_step", "host_transfer",
              "chunk_dispatch", "eval", "encode", "aggregate",
              "client_update", WINDOW)
_SCOPE = re.compile(r"feddd_([a-z_]+)")
_SUFFIX = re.compile(r"[.:_]?\d+$")
_INSTR = re.compile(r"^%?([^\s=]+)\s*=")
_META = re.compile(r'^\s*(?:ROOT\s+)?%([^\s=]+)\s*=.*op_name="([^"]*)"')


@dataclasses.dataclass(frozen=True)
class Op:
    device: int
    start: int          # ns
    end: int            # ns
    name: str
    scope: str          # the instruction's op_name metadata, if known
    program: str        # the XLA module it ran in


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: int
    end: int


@dataclasses.dataclass
class Trace:
    ops: List[Op]
    spans: List[Span]


def phase(op: Op) -> str:
    m = _SCOPE.search(op.scope) or _SCOPE.search(op.name)
    if m:
        return m.group(1)
    if any(op.program.startswith(p) for p in TRAIN_PROGRAMS):
        return "local_train"
    return "other"


def label(op: Op) -> str:
    """The operation's last op_name element, or its instruction name
    without the number."""
    return op.scope.rsplit("/", 1)[-1] if op.scope \
        else _SUFFIX.sub("", op.name)


# ---------------------------------------------------------------- loading

class ScopeMap:
    """Records, from now until :meth:`stop`, every executable JAX compiles
    or loads from its persistent cache, as ``modules[name][instruction]
    = op_name`` from the optimized HLO's metadata.  (It wraps JAX's
    ``compile_or_get_cached``, the one function both paths go through.)"""

    def __init__(self):
        from jax._src import compiler
        self._compiler = compiler
        self._orig = compiler.compile_or_get_cached
        self.modules: Dict[str, Dict[str, str]] = {}

        def record(*a, **k):
            exe = self._orig(*a, **k)
            for mod in exe.hlo_modules():
                table = self.modules.setdefault(mod.name, {})
                for line in mod.to_string().splitlines():
                    m = _META.match(line)
                    if m:
                        table.setdefault(m.group(1), m.group(2))
            return exe

        compiler.compile_or_get_cached = record

    def stop(self) -> None:
        self._compiler.compile_or_get_cached = self._orig


def _device_id(plane_name: str) -> Optional[int]:
    m = re.match(r"/device:TPU:(\d+)", plane_name)
    return int(m.group(1)) if m else None


def load(trace_dir: Path, scopes: Optional[Dict] = None) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir`` as a :class:`Trace`;
    ``scopes`` is a :class:`ScopeMap`'s ``modules``."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    ops: List[Op] = []
    spans: List[Span] = []
    for plane in data.planes:
        dev = _device_id(plane.name)
        lines = {line.name: line for line in plane.lines}
        if dev is not None and "XLA Ops" in lines:
            modules = sorted(
                (int(e.start_ns), int(e.start_ns + e.duration_ns),
                 e.name.split("(")[0])
                for e in lines["XLA Modules"].events) \
                if "XLA Modules" in lines else []
            starts = [m[0] for m in modules]
            for e in lines["XLA Ops"].events:
                start = int(e.start_ns)
                j = bisect.bisect_right(starts, start) - 1
                prog = modules[j][2] if j >= 0 and start < modules[j][1] \
                    else ""
                m = _INSTR.match(e.name)
                instr = m.group(1) if m else e.name
                ops.append(Op(dev, start, start + int(e.duration_ns),
                              instr, (scopes or {}).get(prog, {})
                              .get(instr, ""), prog))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        s = int(e.start_ns)
                        spans.append(Span(e.name, s,
                                          s + int(e.duration_ns)))
    return Trace(ops, spans)


# ---------------------------------------------------------------- reduce

def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def span_at(spans: Sequence[Span], t: float) -> str:
    """The innermost host span open at ``t`` (latest start), or "none"."""
    best = None
    for sp in spans:
        if sp.start <= t < sp.end and (best is None or sp.start > best.start):
            best = sp
    return best.name if best else "none"


def reduce(trace: Trace, devices: Sequence[int]) -> Dict:
    """Busy and idle time inside the ``WINDOW`` span, averaged over
    ``devices``; device seconds by phase and by operation; host span
    seconds.  All times in seconds."""
    win = [s for s in trace.spans if s.name == WINDOW]
    if not win:
        raise ValueError(f"the trace has no {WINDOW!r} host span")
    lo, hi = win[-1].start, win[-1].end
    inner = [s for s in trace.spans if s.name != WINDOW
             and s.end > lo and s.start < hi]
    k = float(len(devices))
    busy = 0.0
    gaps: Dict[str, float] = defaultdict(float)
    by_phase: Dict[str, float] = defaultdict(float)
    by_op: Dict[str, float] = defaultdict(float)
    for dev in devices:
        ops = sorted((op for op in trace.ops if op.device == dev
                      and op.end > lo and op.start < hi),
                     key=lambda o: (o.start, -o.end))
        for i, op in enumerate(ops):
            if i + 1 < len(ops) and ops[i + 1].start < op.end \
                    and ops[i + 1].end <= op.end:
                continue            # a loop's own event: count its body
            s, e = _clip(op.start, op.end, lo, hi)
            ph = phase(op)
            by_phase[ph] += (e - s) / 1e9 / k
            by_op[f"{ph}/{label(op)}"] += (e - s) / 1e9 / k
        merged = union(_clip(op.start, op.end, lo, hi) for op in ops)
        busy += sum(e - s for s, e in merged) / 1e9 / k
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps[span_at(inner, (s + e) / 2)] += (e - s) / 1e9 / k
    host: Dict[str, float] = defaultdict(float)
    for sp in inner:
        s, e = _clip(sp.start, sp.end, lo, hi)
        host[sp.name] += (e - s) / 1e9
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy,
            "idle_gaps": dict(gaps), "phases": dict(by_phase),
            "ops": dict(by_op), "host": dict(host)}


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
