"""Find the highest open-loop rate a cell sustains: one engine, one process,
the cell's mix at each rate in turn.

    python3 -m chipbench.sweep --workload drrl-paper.chat --seed 1 \\
        --seconds 30 --rates 2,3,4,5,6

For each rate it prints one JSON line: requests due in the window, how
many finished, the window's completion rate, the engine's depth (queued
plus admitted) when the window opened and when it closed, and TTFT
percentiles for the first and the last third of the window. A rate is
sustained when the queue does not grow across the window: the depth at
the close is within two requests of the depth at the open, and the TTFT
of the last third is at most 1.5 times the first third's. (Completions
inside a window shorter than a request's life lag arrivals at any rate,
so they are printed, not judged.) The cell then runs at 0.8 times the
highest sustained rate, written into its mix as data.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from chipbench import manifest, model, run, traffic


def sweep(workload: str, seed: int, seconds: float, rates):
    bench = manifest.Bench.load()
    cell = bench.cell(workload)
    spec = model.load_spec(bench.config_path(cell.config))
    mix = traffic.load_mix(bench.mix_path(cell.traffic))
    if mix["loop"] != "open":
        raise SystemExit("the sweep is for open-loop mixes")
    sys.path.insert(0, str(run.ROOT / "src"))
    import jax
    run.check_devices(jax, cell.chips)
    jax.config.update("jax_compilation_cache_dir", str(run.ROOT / ".jax_cache"))
    from repro.core.drrl import init_agent
    from repro.serve.api import Engine, EngineConfig
    from repro.serve.frontend import FrontEnd
    cfg = run.model_config(spec)
    params = model.init_params(spec, seed)
    policy = init_agent(jax.random.fold_in(model.seed_key(seed), 1),
                        cfg.rank, cfg.d_model)
    eng = Engine(cfg, params, policy, config=EngineConfig(
        n_slots=spec.n_slots, max_len=spec.max_len,
        max_new_cap=spec.max_new_cap))
    front = FrontEnd(eng)
    out = []
    try:
        for rate in rates:
            sched = traffic.generate(mix, seed, seconds, spec.vocab,
                                     max_len=spec.max_len,
                                     max_new_cap=spec.max_new_cap,
                                     rate_per_s=rate)
            run.warm_eager(eng.core, sched)
            depth = {}

            def on_window(t0, depth=depth):
                depth["open"] = eng.depth

            reqs, t0, t1 = run.serve_open(front, eng.core, sched, seconds,
                                          spec, on_window)
            depth["close"] = eng.depth
            for r in reqs:
                if r.handle is not None and not r.handle.done:
                    r.handle.cancel()
            front.drain(timeout=120)
            win = [r for r in reqs if r.in_window]
            third = seconds / 3

            def ttft(lo, hi):
                v = [r.first - r.due for r in win if r.first is not None
                     and lo <= r.due - t0 < hi]
                return float(np.percentile(v, 95)) if v else None

            done_in = sum(1 for r in reqs if r.finished
                          and t0 <= r.times[-1] < t1)
            rec = {"rate_per_s": rate, "due_in_window": len(win),
                   "finished_in_window": done_in,
                   "completion_rate": done_in / seconds,
                   "no_first_token": sum(1 for r in win if r.first is None),
                   "depth_open": depth["open"], "depth_close": depth["close"],
                   "ttft_p95_first_third_s": ttft(0, third),
                   "ttft_p95_last_third_s": ttft(2 * third, seconds)}
            f, last = rec["ttft_p95_first_third_s"], \
                rec["ttft_p95_last_third_s"]
            rec["sustained"] = bool(
                rec["no_first_token"] == 0
                and depth["close"] <= depth["open"] + 2
                and f is not None and last is not None and last <= 1.5 * f)
            print(json.dumps(rec), flush=True)
            out.append(rec)
            time.sleep(1.0)
    finally:
        front.shutdown()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    args = ap.parse_args(argv)
    try:
        sweep(args.workload, args.seed, args.seconds,
              [float(x) for x in args.rates.split(",")])
    except run.Refused as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return e.code
    return 0


if __name__ == "__main__":
    sys.exit(main())
