#!/usr/bin/env python3
"""Read the correctness numbers that a cell's limits are set from.

    python3 chipbench/calibrate.py --workload <name> --seeds 1,2,... \
        [--control-seeds 7,8,9]

In one process, so the set-up is paid once:

- for every ``--seeds`` seed, the program's readings: its timed path at
  the cell's own sizes (pricing: a few launches of the cell's batch,
  the sampled rollouts checked; training: the first three steps), held
  against the plain reference. Their largest is a limit's lower reading.
- for every ``--control-seeds`` seed, the control's readings: the
  reference, put in the program's place, one precision below the
  configuration's (pricing: float32 for float64; training: float8
  products for bfloat16), held against the reference; and, for
  training, the fault a run can have, planted in the reference put in
  the program's place (half of each batch left out). The smallest is a
  limit's upper reading.

Prints one JSON line per reading; the benchmark's runs never call this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
CALIBRATION_LAUNCHES = 2


def emit(kind: str, seed: int, numbers: dict) -> None:
    print(json.dumps({"kind": kind, "seed": seed, **numbers}), flush=True)


def price(cell, seeds, control_seeds) -> None:
    import numpy as np

    drv = _driver(cell)
    scales = cell.traffic["scales"]

    class Stub:  # the parts of a run the pricing helpers read
        pass

    for kind, seed in ([("program", s) for s in seeds]
                       + [("control", s) for s in control_seeds]):
        run = Stub()
        run.cell, run.seed = cell, seed
        st = drv.setup(run)
        st.results = [(k, drv.completions(drv._launch(st, k)))
                      for k in range(CALIBRATION_LAUNCHES)]
        worst = 0.0
        for li, r in drv.sample(run, st):
            k, fc = st.results[li]
            want = drv.reference_completions(st, k, r, scales)
            got = (fc[r] if kind == "program" else
                   drv.reference_completions(st, k, r, scales, np.float32))
            worst = max(worst, drv._rel_err(got, want))
        emit(kind, seed, {"flow_completion_rel_err": worst})


def train(cell, seeds, control_seeds) -> None:
    drv = _driver(cell)
    st = drv.build(cell)
    for seed in seeds:
        t0 = time.perf_counter()
        drv.start(st, seed)
        got = drv.program(st)
        st.state, st.batches = None, []
        want = drv.reference(st, against=got["first_grads"])
        emit("program", seed, {**drv.readings(got, want),
                               "seconds": time.perf_counter() - t0})
    rows = int(cell.traffic["per_agent_batch"]) // 2
    for seed in control_seeds:
        st.seed = seed
        planted = {"control": drv.reference(st, "fp8", keep_first=True),
                   "fault_half_batch": drv.reference(st, rows=rows,
                                                     keep_first=True)}
        for kind, got in planted.items():
            want = drv.reference(st, against=got["first_grads"])
            emit(kind, seed, drv.readings(got, want))


def _driver(cell):
    from chipbench.bench import Spec

    return Spec(ROOT).driver(cell)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from chipbench.bench import Spec

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = Spec(ROOT).cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = [int(s) for s in args.control_seeds.split(",") if s]
    {"price": price, "train": train}[cell.driver](cell, seeds, control)
    return 0


if __name__ == "__main__":
    sys.exit(main())
