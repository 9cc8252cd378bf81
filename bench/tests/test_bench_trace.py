"""The trace reduction on a small hand-made trace: busy time is the
union of device intervals, idle gaps are named by the host span open at
the time, device time is grouped by named scope or by program."""

import pytest

from bench import trace as tr
from bench.trace import Op, Span, Trace

MS = 1_000_000  # ns


def _fixture():
    ops = [
        # overlapping ops on device 0: union 10-40 ms
        Op(0, 10 * MS, 30 * MS, "fusion.1", "jit(run)/feddd_local_train/conv",
           "jit_run_rounds"),
        Op(0, 20 * MS, 40 * MS, "fusion.2", "", "jit_batched"),
        # the server step: aggregate then allocate, 60-70 ms and 70-75 ms
        Op(0, 60 * MS, 70 * MS, "reduce.3", "jit(step)/feddd_aggregate/add",
           "jit__round_step"),
        Op(0, 70 * MS, 75 * MS, "copy.4", "feddd_allocate", "jit_run"),
        # an op outside the window is left out
        Op(0, 120 * MS, 130 * MS, "fusion.5", "", "jit_eval"),
        # device 1: one op 0-50 ms, clipped to the window at 5 ms
        Op(1, 0, 50 * MS, "all-reduce.6", "", "jit_step"),
    ]
    spans = [Span(tr.WINDOW, 5 * MS, 105 * MS),
             Span("local_train", 5 * MS, 45 * MS),
             Span("allocate", 45 * MS, 58 * MS),
             Span("host_transfer", 80 * MS, 100 * MS)]
    return Trace(ops, spans)


def test_union_merges_overlaps():
    assert tr.union([(0, 5), (3, 8), (10, 12), (12, 13)]) == [(0, 8),
                                                              (10, 13)]


def test_busy_window_and_gaps_named_by_open_span():
    red = tr.reduce(_fixture(), [0])
    assert red["window_s"] == pytest.approx(0.100)
    assert red["busy_s"] == pytest.approx(0.030 + 0.015)
    gaps = red["idle_gaps"]
    assert gaps["local_train"] == pytest.approx(0.005)      # 5-10 ms
    assert gaps["allocate"] == pytest.approx(0.020)         # 40-60 ms
    assert gaps["host_transfer"] == pytest.approx(0.030)    # 75-105 ms
    assert sum(gaps.values()) == pytest.approx(0.100 - 0.045)


def test_phases_by_scope_then_training_program():
    red = tr.reduce(_fixture(), [0])
    ph = red["phases"]
    assert ph["local_train"] == pytest.approx(0.040)  # scope + jit_batched
    assert ph["aggregate"] == pytest.approx(0.010)
    assert ph["allocate"] == pytest.approx(0.005)
    assert "other" not in ph
    assert red["ops"]["local_train/conv"] == pytest.approx(0.020)
    assert red["ops"]["local_train/fusion"] == pytest.approx(0.020)
    assert red["host"]["allocate"] == pytest.approx(0.013)


def test_a_loop_counts_its_body_not_itself():
    ops = [Op(0, 0, 10 * MS, "while.1", "", "jit_run"),
           Op(0, 1 * MS, 4 * MS, "fusion.2", "x/feddd_local_train/conv",
              "jit_run"),
           Op(0, 5 * MS, 9 * MS, "fusion.3", "x/feddd_aggregate/add",
              "jit_run")]
    red = tr.reduce(Trace(ops, [Span(tr.WINDOW, 0, 10 * MS)]), [0])
    assert red["busy_s"] == pytest.approx(0.010)
    assert red["phases"] == pytest.approx({"local_train": 0.003,
                                           "aggregate": 0.004})
    assert set(red["ops"]) == {"local_train/conv", "aggregate/add"}


def test_devices_are_averaged():
    red = tr.reduce(_fixture(), [0, 1])
    # device 1 busy 5-50 ms = 45 ms, device 0 45 ms
    assert red["busy_s"] == pytest.approx(0.045)
    assert red["phases"]["other"] == pytest.approx(0.045 / 2)


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce(Trace([], [Span("allocate", 0, 1)]), [0])


def test_top_orders_by_time():
    assert tr.top({"a": 1.0, "b": 3.0, "c": 2.0}, 2) == [["b", 3.0],
                                                         ["c", 2.0]]
