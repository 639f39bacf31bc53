#!/usr/bin/env python3
"""Readings a cell's logit-gap limit is set from, for a configuration of
any architecture, on the chip.

    python3 benchmarks/chip/readings.py --workload <cell> --seconds <s> \
        --seeds 101 102 ... [--controls int8 fp8] [--trace 1] [--patch JSON]

For each seed, one run of the cell as ``run.py`` makes it, then the
reference over the sampled served tokens: the program's widest gap and
each control's widest gap at the same positions (the reference with its
matrices in int8 or fp8). ``control.py`` does the same for the Qwen cells
and also compares corners of their weights; this script leaves that out,
since the weight trees differ between architectures (the CPU tests check
that a reference draws the program's weights bit for bit). Where the
reference returns more (``readings``: the same reading at other routing
margins, with the positions kept and the flips), it is printed too, and
so is the engine's MoE count where it keeps one. With ``--trace 1`` the
runs are traced and report the cell's per-layer metrics. All seeds run in
one process. Prints one JSON line per seed and a summary line last: the
largest program reading and, per control, the smallest reading, at the
check's margin and at each other one.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="*", default=["int8", "fp8"],
                    choices=["int8", "fp8"])
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--patch", type=json.loads, default=None)
    args = ap.parse_args(argv)
    from chipbench import cell
    checked = []

    def run_check(*a, **kw):
        checked.append(cell_run_check(*a, **kw))
        return checked[-1]
    cell_run_check, cell.run_check = cell.run_check, run_check
    engines = []
    prog, ctrl, sweeps = [], [], []
    for seed in args.seeds:
        try:
            out = cell.run_cell(args.workload, seed, args.seconds,
                                bool(args.trace), t_start=time.perf_counter(),
                                patch=args.patch, engine_hook=engines.append,
                                controls=tuple(args.controls))
        except cell.NoChip as e:
            print(f"readings.py: {e}", file=sys.stderr)
            return 2
        gap = out["checks"]["max_logit_gap"]["value"]
        prog.append(gap)
        ctrl.append(out.get("control_max_logit_gap", {}))
        sweeps.append(checked[-1].get("readings", {}))
        metrics = engines[-1].metrics.summary()
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "program_max_gap": gap, "control_max_gap": ctrl[-1],
                          "tokens": out["checks"]["tokens_compared"]["value"],
                          "settled": checked[-1].get("settled"),
                          "readings": sweeps[-1],
                          "moe": metrics.get("moe"),
                          "decode_steps": metrics.get("decode_steps"),
                          "metrics": out["metrics"], "device": out["device"],
                          "breakdown": out.get("breakdown")}),
              flush=True)
        engines.clear()
    lows = {k: min(c[k] for c in ctrl) for k in args.controls}
    by_margin = {at: {"program_max": max(s[at]["program"] for s in sweeps),
                      "kept_min": min(s[at]["kept"] for s in sweeps),
                      "flips_settled_max": max(
                          s[at]["flips"] - s[at]["flips_unsettled"]
                          for s in sweeps),
                      **{f"{k}_min": min(s[at][k] for s in sweeps)
                         for k in args.controls}}
                 for at in (sweeps[0] if all(sweeps) else {})}
    print(json.dumps({"workload": args.workload, "seeds": len(prog),
                      "program_max": max(prog), "control_min": lows,
                      "by_margin": by_margin}),
          flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
