"""The control, at a size a CPU test run holds: the configuration's
reference one precision step down, put in the program's place, reads far
above the program. On the chip, at the cells' own size,
``bench/tests/control.py`` gives the readings the limits are set from; the
ResNet control (float32 at ``"high"`` against ``"highest"``) is read only
there, since a CPU computes both in float32."""
import pytest

import run as bench_run
from conftest import tiny_cell
from control import with_control

SEED = 2 ** 31 + 99


@pytest.mark.parametrize("name, key", [("qwen2-1.5b.chat",
                                        "token_logit_gap")])
def test_control_reads_above_the_program(name, key, fresh_programs):
    cell = tiny_cell(name)
    with_control(cell)
    checks = bench_run.execute(cell, SEED, 2.0, trace=False)["checks"]
    program, control = checks[key]["value"], checks[f"control.{key}"]["value"]
    assert control > 10 * program and control > 0, checks
