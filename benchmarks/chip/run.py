#!/usr/bin/env python3
"""Run one cell of the on-chip serving benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Builds the cell's configuration through ``ServeEngine.build`` (matrices
from the configuration's fixed ``weights_seed``, biases and norm weights
from ``--seed``), makes the traffic from ``--seed``, warms up every shape
the traffic reaches, drives it for a pre-roll and then the measured
window of ``--seconds``, and checks the served tokens against the plain
float32 reference. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics from a profiler trace
of the window), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``: each number compared beside its limit, which also end standard
error. Without a TPU, or with fewer chips than the cell asks for, it exits
with 2 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from chipbench.cell import NoChip, run_cell
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=T_START)
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
