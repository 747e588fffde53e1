"""The control comes out not correct: the plain reference, put in the
program's place and computed one precision below the configuration's,
fails at least one of its cell's limits (here at a size a test can
hold; on the chip at the cell's own size by ``chipbench/calibrate.py``).
"""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from test_chipbench_faults import PRICE, SEED, driver, small_cell  # noqa: E402


def test_float32_pricing_fails_the_float64_limit():
    cell = small_cell(PRICE)
    drv = driver(cell)

    class Run:
        pass

    run = Run()
    run.cell, run.seed = cell, SEED
    st = drv.setup(run)
    worst = 0.0
    for r in range(4):
        want = drv.reference_completions(st, 0, r, cell.traffic["scales"])
        got = drv.reference_completions(st, 0, r, cell.traffic["scales"],
                                        np.float32)
        worst = max(worst, drv._rel_err(got, want))
    assert worst > cell.limits["flow_completion_rel_err"]


@pytest.mark.parametrize("workload", ["train.qwen2-0.5b.solo",
                                      "train.qwen1.5-0.5b.solo"])
def test_float8_training_fails_a_limit(workload):
    cell = small_cell(workload)
    drv = driver(cell)
    st = drv.build(cell)
    st.seed = SEED
    control = drv.reference(st, "fp8", keep_first=True)
    numbers = drv.readings(
        control, drv.reference(st, against=control["first_grads"]))
    failed = [k for k, limit in cell.limits.items() if numbers[k] > limit]
    assert failed, numbers
