import os
import sys
from pathlib import Path

# NOTE: do NOT set --xla_force_host_platform_device_count here — smoke tests
# and benches must see the real single device; only launch/dryrun.py forces
# 512 placeholder devices (and only in its own process).

# XLA:CPU fuses a*b+c into one FMA and vectorizes reductions at the
# widest vector ISA the host has, so two differently compiled programs of
# the same float32 arithmetic agree bit for bit on one host and differ by
# an ulp on another.  Pinning the ISA its generated code may use keeps
# the exact-equality contracts of that code (engine vs reference loop,
# fused step vs a hand computation) from depending on the host.
_ISA = "--xla_cpu_max_isa=AVX"
_FLAGS = os.environ.get("XLA_FLAGS", "")
if _ISA not in _FLAGS:
    os.environ["XLA_FLAGS"] = f"{_FLAGS} {_ISA}".strip()

SRC = Path(__file__).resolve().parents[1] / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))
