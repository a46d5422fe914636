"""The control at a size a test run can hold: the reference computed with
fp8 matmul operands in the program's place must read above the program
on the number each cell compares, by the factor the limits are set with
(three), and come out not correct under the cell's own checks and
limits, as it does on the chip at the cells' own sizes (PERF.md).

Each window is one unit of work (``--seconds 0``), repeated ``units``
times, so that the requests or steps compared do not depend on how fast
the CPU runs."""

import gc

import pytest

import chipbench_util  # noqa: F401  (puts the benchmark on sys.path)
import harness


@pytest.mark.parametrize("cell,number,units", [
    ("zamba2.gen", "served_gap", 1),
    ("zamba2.ttft-4k", "served_gap", 16),
    ("mamba2.train-4k", "loss_gap", 1),
])
def test_control_reads_above_program(checkout, cell, number, units):
    import run
    _, _, r = run.prepare(cell, 5, **checkout.where())
    r.setup()
    for _ in range(units):
        r.window(0.0)
    r.free()
    gc.collect()
    assert r.failed == 0
    checks = r.check()
    assert harness.correct(checks)
    program = {c.name: c.value for c in checks}
    controls = r.control()
    assert not harness.correct(controls)
    control = {c.name: c.value for c in controls}
    assert control[number] > 3 * program[number]
