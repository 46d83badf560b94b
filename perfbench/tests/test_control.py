"""The control, at a size a test run holds: the reference one precision
below the configuration, in the program's place, reads wider gaps than
the program on every seed, and fails the limit that sound runs pass.
On the chip the same readings, at each cell's size, set the limits in
`perfbench/limits/` (see PERF.md)."""
import pytest

from perfbench import calibrate, driver
from perfbench.tests.conftest import load

# Mean gaps on the CPU under the backlog mix: small bf16 (d 512, 4
# layers) reads 0.0001-0.0007 on these seeds, its int8 control
# 0.0033-0.0055; tiny w8a8 reads < 0.002, its int4 control > 0.26.
# (At d 64 the bf16 program and its int8 control overlap.)
LIMITS = {"small": 0.002, "tiny-w8a8": 0.02}


@pytest.mark.parametrize("name,mix", [("small", "batch"),
                                      ("tiny-w8a8", "batch")])
def test_control_fails_where_program_passes(name, mix):
    cfg, counter = load(name), driver.CompileCounter()
    for seed in (1, 2, 3):
        r = calibrate.reading(cfg, load(mix), seed, 3.0, counter, True)
        assert r["program"] <= LIMITS[name] < r["control"], r
