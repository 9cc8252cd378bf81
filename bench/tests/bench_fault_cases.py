"""The comparison that decides ``correct``, driven through the rest of a
run (no look for a chip) on a cell cut to a CPU size: the program as the
cell runs it is correct; the control (the reference in bfloat16) and each
fault planted under the timed path are not.  ``test_bench_faults_*.py``
instantiate these per cell, one file each so that test workers share
them out."""

import jax
import pytest

from bench import compare, control, run
from bench_tiny import CPU, tiny_cell

FAULTS = ("frozen", "half_clients", "altered_rates")
SEED = 2 ** 31 + 12345   # more than 32 signed bits hold


@pytest.fixture
def fresh_programs():
    """Compiled programs traced before or during a planted fault must not
    serve another test."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def cases(cell: str) -> dict:
    def test_program_is_correct(fresh_programs):
        out = run.run_cell(tiny_cell(cell), SEED, 0.5, False, CPU)
        assert out["correct"], out["checks"]
        assert out["failed"] == 0 and out["window"]["compiles"] == 0
        assert list(out)[-1] == "checks"

    def test_control_is_not_correct():
        c = tiny_cell(cell)
        checks = compare.judge(control.readings(c, SEED, "control")[0],
                               c["limits"])
        assert not all(ch["ok"] for ch in checks), checks

    @pytest.mark.parametrize("fault", FAULTS)
    def test_fault_is_not_correct(fault, monkeypatch, fresh_programs):
        control.plant(fault, monkeypatch.setattr)
        out = run.run_cell(tiny_cell(cell), SEED, 0.5, False, CPU)
        assert not out["correct"], out["checks"]

    return {"fresh_programs": fresh_programs,
            "test_program_is_correct": test_program_is_correct,
            "test_control_is_not_correct": test_control_is_not_correct,
            "test_fault_is_not_correct": test_fault_is_not_correct}
