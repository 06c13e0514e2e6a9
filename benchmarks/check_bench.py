"""Perf-regression gate over BENCH_serve.json.

Compares a freshly generated serving benchmark (normally
``python -m benchmarks.serve_bench --smoke --out fresh.json`` in CI)
against the committed full-run baseline, with a per-metric tolerance
band. Bands are deliberately scale-free or structural: the smoke
workload is far smaller than the committed run and CI runners are
slower/noisier than the box that produced the baseline, so each band
is wide enough to absorb that — while still failing on order-of-kind
regressions (batching broken, prefix cache not reusing, factor path
reading dense bytes, affinity routing not beating round-robin).

    python -m benchmarks.check_bench fresh.json            # gate
    python -m benchmarks.check_bench fresh.json --baseline BENCH_serve.json

Exit status is non-zero iff any gated metric is out of band. To
re-baseline after an intentional perf change, run the full bench on a
quiet machine and commit the result:

    python -m benchmarks.serve_bench --out BENCH_serve.json
"""
import argparse
import json
import sys


def _get(d, path):
    for k in path.split("."):
        if d is None:
            return None
        d = d.get(k)
    return d


# (path, kind, band) — kind:
#   "flag"      value must be truthy in the fresh run
#   "min_ratio" fresh >= band * baseline
#   "max_ratio" fresh <= band * baseline
#   "min_abs"   fresh >= band (baseline shown for context only)
#   "max_abs"   fresh <= band (baseline shown for context only)
#   "eq_abs"    fresh == band exactly (deterministic counters only)
#   "info"      reported, never gated (wall-clock on shared runners)
CHECKS = [
    ("prefix_cache.parity", "flag", None,
     "prefix-cache hit is token-identical to cold admission"),
    ("factor_cache.parity_full_rank", "flag", None,
     "factored decode matches dense at full rank"),
    ("speedup", "min_ratio", 0.20,
     "continuous batching vs sequential (smoke under-saturates the slots)"),
    ("prefix_cache.prefill_token_reduction", "min_ratio", 0.5,
     "prefill tokens cut by shared-prefix reuse"),
    ("prefix_cache.cached.hit_rate", "min_ratio", 0.7,
     "radix-tree hit rate on the shared-prefix workload"),
    ("factor_cache.low_rank.read_ratio", "max_ratio", 1.05,
     "K-cache bytes/token, factored vs dense (r_keep/dh, deterministic)"),
    ("router.hit_rate_gain", "min_abs", 0.10,
     "affinity hit-rate minus round-robin (must stay decisively positive)"),
    ("speculative.parity", "flag", None,
     "speculative decode is token-identical to plain decode"),
    ("speculative.accept_rate", "min_abs", 0.6,
     "quarter-rank draft accept rate (deterministic given model/workload)"),
    ("speculative.mean_accept_len", "min_abs", 1.3,
     "tokens per fused dispatch (plain decode is exactly 1.0; this is "
     "the speedup factor wherever per-step cost dominates)"),
    ("speculative.tok_per_s_ratio", "info", None,
     "speculative vs plain tok/s (toy-scale CPU wall-clock: the drafts' "
     "rank cut saves attention reads, which are negligible here — "
     "report, don't gate)"),
    ("router.tok_per_s_ratio_vs_single", "info", None,
     "2-replica aggregate vs 1 replica (wall-clock: report, don't gate)"),
    ("engine.tok_per_s", "info", None,
     "absolute throughput (runner-speed dependent)"),
    # learned rank policy: trace -> offline train -> replay. Reward and
    # kept rank are deterministic given model + workload suite; the
    # constrained oracle dominates the adaptive heuristic by
    # construction, so a trained policy that loses reward or inflates
    # rank has failed to fit — that's a regression, not noise
    ("learned_policy.replay.valid", "flag", None,
     "mode='learned' serves the full replay suite with valid streams"),
    ("learned_policy.reward_gain", "min_abs", -0.002,
     "learned Eq. 13 reward must match/beat the adaptive heuristic "
     "(small band = BC fit tolerance)"),
    ("learned_policy.rank_ratio", "max_abs", 1.0005,
     "learned/adaptive mean kept rank — the policy may not buy reward "
     "with extra factor-read bytes (trainer's constrained snapshot "
     "selection guarantees <= 1 whenever any snapshot achieves it)"),
    ("learned_policy.agreement_gain", "info", None,
     "retained-energy agreement, learned minus adaptive"),
    ("learned_policy.replay.serve_rank_ratio", "info", None,
     "kept-rank ratio during live replay (policy feeds back into its "
     "own prev-rank state: report, don't gate)"),
    # runtime sanitizer lane: deterministic counters, gated EXACTLY —
    # one extra executable in steady state is a latency cliff, not noise
    ("compile_guard.ok", "flag", None,
     "transfer-guarded fused steps ran clean (no implicit host sync)"),
    ("compile_guard.mixed_sampling.steady_new_executables", "eq_abs", 0,
     "zero new executables across the steady mixed greedy/top-k/top-p run"),
    ("compile_guard.speculative.steady_new_executables", "eq_abs", 0,
     "zero new executables across the steady draft/verify + rank-switch run"),
    ("compile_guard.learned_policy.steady_new_executables", "eq_abs", 0,
     "zero new executables across the steady mode='learned' run (the "
     "policy net rides the jitted decide executable)"),
    ("compile_guard.observability.steady_new_executables", "eq_abs", 0,
     "metrics + span tracing ON adds zero executables to the steady "
     "serving loop (repro.obs hooks are pure host Python)"),
    ("compile_guard.mixed_sampling.warm_executables", "max_ratio", 1.0,
     "warmup executable count must not grow past the committed baseline"),
    ("compile_guard.speculative.warm_executables", "max_ratio", 1.0,
     "warmup executable count must not grow past the committed baseline"),
    ("compile_guard.learned_policy.warm_executables", "max_ratio", 1.0,
     "warmup executable count must not grow past the committed baseline"),
    ("compile_guard.observability.warm_executables", "max_ratio", 1.0,
     "warmup executable count must not grow past the committed baseline"),
    # observability overhead lane: parity is a hard gate, the timing
    # ratio is report-only (host-timer noise at smoke scale)
    ("obs.parity", "flag", None,
     "token streams identical with obs tracing enabled vs disabled"),
    ("obs.on_off_ratio", "info", None,
     "decode tok/s with tracing on over tracing off (report-only)"),
    ("obs.trace_events", "info", None,
     "Chrome trace events recorded for the bench workload"),
    ("obs.trace_dropped", "eq_abs", 0,
     "the bench workload must fit the trace ring (no dropped events)"),
]


def check(fresh: dict, baseline: dict):
    rows, failures = [], []
    for path, kind, band, why in CHECKS:
        f, b = _get(fresh, path), _get(baseline, path)
        ok, detail = True, ""
        if f is None:
            ok, detail = False, "missing from fresh run"
        elif kind == "flag":
            ok, detail = bool(f), "must be true"
        elif kind == "info":
            detail = "informational"
        elif kind == "min_abs":
            ok = f >= band
            detail = f">= {band:.3g}"
        elif kind == "max_abs":
            ok = f <= band
            detail = f"<= {band:.3g}"
        elif kind == "eq_abs":
            ok = f == band
            detail = f"== {band}"
        elif b is None:
            ok, detail = False, "missing from baseline"
        elif kind == "min_ratio":
            ok = f >= band * b
            detail = f">= {band:.2f}x baseline ({band * b:.3g})"
        elif kind == "max_ratio":
            ok = f <= band * b
            detail = f"<= {band:.2f}x baseline ({band * b:.3g})"
        rows.append((path, b, f, detail, ok, why))
        if not ok and kind != "info":
            failures.append(path)
    return rows, failures


def _fmt(v):
    if v is None:
        return "-"
    if isinstance(v, bool):
        return str(v)
    if isinstance(v, float):
        return f"{v:.3f}"
    return str(v)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("fresh", help="freshly generated serve-bench JSON")
    ap.add_argument("--baseline", default="BENCH_serve.json",
                    help="committed baseline (default: BENCH_serve.json)")
    args = ap.parse_args(argv)
    with open(args.fresh) as f:
        fresh = json.load(f)
    with open(args.baseline) as f:
        baseline = json.load(f)

    rows, failures = check(fresh, baseline)
    w = max(len(r[0]) for r in rows)
    print(f"{'metric':<{w}}  {'baseline':>10}  {'fresh':>10}  "
          f"{'band':<34} status")
    for path, b, f, detail, ok, why in rows:
        status = "ok" if ok else "FAIL"
        if detail == "informational":
            status = "info"
        print(f"{path:<{w}}  {_fmt(b):>10}  {_fmt(f):>10}  "
              f"{detail:<34} {status}")
        if not ok:
            print(f"{'':<{w}}  -> {why}")

    if failures:
        print(f"\nREGRESSION: {len(failures)} metric(s) out of band: "
              f"{', '.join(failures)}")
        print("If intentional, re-baseline: "
              "python -m benchmarks.serve_bench --out BENCH_serve.json")
        return 1
    print(f"\nall gated metrics within tolerance of {args.baseline}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
