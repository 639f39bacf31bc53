#!/usr/bin/env python3
"""Readings a cell's logit-gap limit is set from, and other one-off runs
of a cell, on the chip.

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \
        --seeds 101 102 ... [--controls int8 fp8] [--patch JSON]

For each seed, one run of the cell as ``run.py`` makes it (the cell's own
configuration, traffic and load, a window of ``--seconds``), then the
reference over the sampled served tokens: the program's widest gap, and
each of ``--controls``' widest gap at the same positions (the reference
with its matrices in int8 or fp8). ``--patch`` changes the cell for these
runs only, as ``run_cell`` merges it: the program's own int8 KV path
(``{"config": {"serve": {"kv_backend": "paged_int8"}}}``), an offered
rate for the sweep that finds the knee (``{"mix": {"rate_rps": 0.6}}``),
or another draw of the mix (``{"mix": {"master_seed": 1}}``). All seeds
run in one process. Prints one JSON line per seed and a summary line
last: the largest program reading (the limit's lower end) and, per
control, the smallest reading (its upper end).
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


def weight_sample(engine) -> dict:
    """Corners of a few of the program's weights, copied to the host."""
    import numpy as np
    p = engine.params
    f = lambda x: np.asarray(x[:64, :64].astype("float32"))  # noqa: E731
    return {"embed": f(p["embed"]["table"]), "unembed": f(p["unembed"]["w"]),
            "wq0": f(p["layers"]["attn"]["wq"][0]),
            "w_down_last": f(p["layers"]["mlp"]["w_down"][-1])}


def weights_diff(cell: str, seed: int, sample: dict) -> float:
    """Largest difference between those corners and the reference's own
    draws of the same weights: 0 when the reference holds the program's
    weights bit for bit."""
    import math
    import numpy as np
    from chipbench.cell import cell_spec, reference_module
    c = cell_spec(cell).config
    r = reference_module(c).Qwen2Reference(
        c["model"], int(c["weights_seed"]), seed)
    k_emb, layer_keys, k_head = r._keys()
    d, *_, vp = r._dims
    got = {"embed": r._table(k_emb, (vp, d), 0.02, 0, None),
           "unembed": r._table(k_head, (d, vp), 1 / math.sqrt(d), -1, None),
           "wq0": r._layer_weights(layer_keys[0], r._layer_small(0),
                                   None)["wq"],
           "w_down_last": r._layer_weights(layer_keys[-1], r._layer_small(
               r.layers - 1), None)["w_down"]}
    return max(float(np.abs(np.asarray(got[k][:64, :64]) - v).max())
               for k, v in sample.items())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="*", default=["int8", "fp8"],
                    choices=["int8", "fp8"])
    ap.add_argument("--patch", type=json.loads, default=None)
    args = ap.parse_args(argv)
    from chipbench.cell import NoChip, run_cell
    prog, ctrl = [], []
    for seed in args.seeds:
        t0 = time.perf_counter()
        sample = {}
        try:
            out = run_cell(args.workload, seed, args.seconds, False,
                           t_start=t0, patch=args.patch,
                           controls=tuple(args.controls),
                           engine_hook=lambda e: sample.update(
                               weight_sample(e)))
        except NoChip as e:
            print(f"control.py: {e}", file=sys.stderr)
            return 2
        out["metrics"]["weights_max_abs_diff"] = weights_diff(
            args.workload, seed, sample)
        gap = out["checks"]["max_logit_gap"]["value"]
        prog.append(gap)
        ctrl.append(out.get("control_max_logit_gap", {}))
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "program_max_gap": gap, "control_max_gap": ctrl[-1],
                          "tokens": out["checks"]["tokens_compared"]["value"],
                          "metrics": out["metrics"]}), flush=True)
    lows = {k: min(c[k] for c in ctrl) for k in args.controls}
    print(json.dumps({"workload": args.workload, "seeds": len(prog),
                      "program_max": max(prog), "control_min": lows,
                      "ratio": {k: v / max(prog) if max(prog) else None
                                for k, v in lows.items()}}),
          flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
