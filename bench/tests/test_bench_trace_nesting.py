"""The trace reduction with host spans nested inside others, as the
program's per-client spans (``client_train``) nest inside ``local_train``:
the outer span's host time and the device's busy time read as without
them, and an idle gap is named by the innermost span open at the time."""

import pytest

from bench import trace as tr
from bench.trace import Op, Span, Trace

MS = 1_000_000  # ns


def _nested_fixture(nested: bool):
    """One round of a per-client loop: ``local_train`` 0-50 ms, the device
    busy 10-20, 30-40 and 50-60 ms; with ``nested`` two ``client_train``
    spans (5-25, 25-45 ms) inside it and a ``fleet_unstack`` span
    (60-90 ms) after it."""
    ops = [Op(0, 10 * MS, 20 * MS, "fusion.1", "", "jit__step"),
           Op(0, 30 * MS, 40 * MS, "fusion.2", "", "jit__step"),
           Op(0, 50 * MS, 60 * MS, "copy.3", "", "jit_run")]
    spans = [Span(tr.WINDOW, 0, 100 * MS),
             Span("local_train", 0, 50 * MS)]
    if nested:
        spans += [Span("client_train", 5 * MS, 25 * MS),
                  Span("client_train", 25 * MS, 45 * MS),
                  Span("fleet_unstack", 60 * MS, 90 * MS)]
    return Trace(ops, spans)


def test_nested_spans_leave_outer_time_and_busy_unchanged():
    flat = tr.reduce(_nested_fixture(False), [0])
    red = tr.reduce(_nested_fixture(True), [0])
    assert red["host"]["local_train"] == flat["host"]["local_train"] \
        == pytest.approx(0.050)
    assert red["busy_s"] == flat["busy_s"] == pytest.approx(0.030)
    assert red["host"]["client_train"] == pytest.approx(0.040)
    assert red["host"]["fleet_unstack"] == pytest.approx(0.030)
    # a gap is named by the innermost span open at its midpoint
    assert flat["idle_gaps"] == pytest.approx({"local_train": 0.030,
                                               "none": 0.040})
    assert red["idle_gaps"] == pytest.approx({
        "client_train": 0.020,      # 0-10 ms (midpoint 5), 20-30 (25)
        "local_train": 0.010,       # 40-50 ms (45: both clients done)
        "fleet_unstack": 0.040})    # 60-100 ms (midpoint 80)
