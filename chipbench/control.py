"""Readings that set a cell's logit-gap limit: the program's widest gap
and each control's, seed by seed, in one process.

    python3 -m chipbench.control --workload drrl-paper.chat \\
        --seeds 101,102,103 --seconds 51 [--controls float8_e4m3fn,bfloat16]

Each seed is one run of the cell (``chipbench.run.run_cell``) at its own
load and sizes. After it the sampled requests are compared with the
reference as served (the program's reading, the lower end of the limit)
and, at the same positions, by the token the reference computed in each
control precision puts first (the configuration's ``check.control``
first: it takes the program's place in the comparison that decides
``correct``, so that run has to come out not correct; its reading is the
upper end). One JSON line per seed. The benchmark's own runs never run
the control.
"""
from __future__ import annotations

import argparse
import json
import sys

from chipbench import manifest, model, run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--controls", default="",
                    help="further precisions to read after the "
                         "configuration's control, comma-separated")
    args = ap.parse_args(argv)
    bench = manifest.Bench.load()
    spec = model.load_spec(bench.config_path(bench.cell(args.workload).config))
    controls = [spec.control] + [c for c in args.controls.split(",")
                                 if c and c != spec.control]
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            res = run.run_cell(args.workload, seed, args.seconds, False,
                               controls=controls)
        except run.Refused as e:
            print(f"chipbench: {e}", file=sys.stderr)
            return e.code
        info = res["info"]
        print(json.dumps({"seed": seed, "gap": info["program_gap"],
                          "control_gaps": info["control_gaps"],
                          "limit": res["checks"]["logit_gap"]["limit"],
                          "correct": res["correct"],
                          "tokens": res["checks"]["tokens_checked"]["value"],
                          "check_s": info["check_s"],
                          "metrics": res["metrics"],
                          "sample": info["sample"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
