"""``correct`` of cell vgg_full_128.per_round_eval at a CPU size: the program passes, the
control and each planted fault fail (bench_fault_cases.py)."""

from bench_fault_cases import cases

globals().update(cases("vgg_full_128.per_round_eval"))
