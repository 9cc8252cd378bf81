"""``correct`` of cell vgg_hetero_a_40.grouped at a CPU size: the program passes, the
control and each planted fault fail (bench_fault_cases.py)."""

from bench_fault_cases import cases

globals().update(cases("vgg_hetero_a_40.grouped"))
