"""The control: the plain reference put in the program's place in float8
matmuls (the step below the configurations' bfloat16) fails the check, at
a size a test run holds, on three seeds. The same readings on the chip at
the cells' own sizes are in PERF.md."""
import jax
import pytest

from bench_tiny import LIMITS, tiny_cell
from bench.harness import check, reference


@pytest.mark.parametrize("config", ["tiny-mla", "tiny-ssd"])
def test_fp8_control_fails(config):
    cell = tiny_cell(config)
    ref = reference.Reference(cell.config, cell.traffic)
    control = reference.Reference(cell.config, cell.traffic, precision="fp8")
    devices = jax.devices()[:1]
    for seed in (11, 12, 3_000_000_019):
        numbers = check.gaps(control.run(seed, devices), ref.run(seed, devices))
        correct, rows = check.judge(numbers, LIMITS)
        assert not correct, rows
