"""``correct`` under the client-sharded placement at a CPU size, on four
host devices: ``vgg_full_128``'s client, settings and limits under the
``sharded`` traffic mix (the fleet client-sharded over a 1-D ``clients``
mesh, the reference's clients spread over the same devices).  The
program so placed is correct; the control (the reference in bfloat16)
and each fault planted under the timed path are not, the exchange
between the chips left out among them.

The cases run in one subprocess with
``--xla_force_host_platform_device_count=4``, so that the test process
keeps its one device; each prints one JSON line that a test reads."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BASE = "vgg_full_128.scanned"
FAULTS = ("frozen", "half_clients", "altered_rates", "no_exchange")
SEED = 2 ** 31 + 12345   # more than 32 signed bits hold

CASES = textwrap.dedent("""
    import json, sys
    root, base, seed, faults = (sys.argv[1], sys.argv[2], int(sys.argv[3]),
                                sys.argv[4].split(","))
    sys.path[:0] = [root, root + "/src", root + "/bench/tests"]
    import jax
    from bench import compare, control, run
    from bench_tiny import CPU, tiny_cell
    device = dict(CPU, count=4)

    def sharded():
        cell = tiny_cell(base)
        with open(root + "/bench/traffic/sharded.json") as f:
            cell.update(name=base.split(".")[0] + ".sharded",
                        traffic="sharded", chips=4, traffic_data=json.load(f))
        return cell

    def emit(case, out):
        print(json.dumps({"case": case, "correct": out["correct"],
                          "failed": out["failed"],
                          "compiles": out["window"]["compiles"],
                          "last_key": list(out)[-1],
                          "checks": out["checks"]}), flush=True)

    assert len(jax.devices()) == 4, jax.devices()
    emit("program", run.run_cell(sharded(), seed, 0.5, False, device))
    c = sharded()
    checks = compare.judge(control.readings(c, seed, "control")[0],
                           c["limits"])
    print(json.dumps({"case": "control",
                      "correct": all(ch["ok"] for ch in checks),
                      "checks": checks}), flush=True)
    for fault in faults:
        with control.planted(fault):
            emit(fault, run.run_cell(sharded(), seed, 0.5, False, device))
""")


@pytest.fixture(scope="module")
def cases():
    flags = os.environ.get("XLA_FLAGS", "").split()
    env = dict(os.environ, XLA_FLAGS=" ".join(
        flags + ["--xla_force_host_platform_device_count=4"]))
    proc = subprocess.run(
        [sys.executable, "-c", CASES, str(ROOT), BASE, str(SEED),
         ",".join(FAULTS)],
        capture_output=True, text=True, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return {d["case"]: d for d in map(json.loads, (
        line for line in proc.stdout.splitlines() if line.startswith("{")))}


def test_program_is_correct(cases):
    out = cases["program"]
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["compiles"] == 0
    assert out["last_key"] == "checks"


def test_control_is_not_correct(cases):
    assert not cases["control"]["correct"], cases["control"]["checks"]


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_not_correct(fault, cases):
    assert not cases[fault]["correct"], cases[fault]["checks"]
