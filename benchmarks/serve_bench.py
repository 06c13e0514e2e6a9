"""Serving benchmark: continuous batching vs sequential lock-step decode.

Synthetic multi-user workload — mixed prompt lengths, staggered arrivals —
decoded twice:

  * **engine**: one ServeEngine with n_slots concurrent lanes (the
    continuous-batching path: slot-paged cache, per-slot dynamic ranks,
    one fused executable);
  * **sequential**: the same requests served one at a time through a
    1-slot ``repro.serve.api.Engine`` (per-request lock-step decode), the
    way a single-stream server would drain the queue.

Both sides are warmed first; compilation is reported separately and
excluded from throughput. Emits aggregate tok/s and p50/p95 per-token
decode latency as JSON to BENCH_serve.json.

A third section compares the **factor-form paged K cache** (kt = K . B_r)
against the dense paged path: token parity is asserted at full rank, and
the score-contraction read bytes per decoded token are recorded for a
low-rank serving grid (r_max/d of the dense K bytes; the wall-clock gap
only opens on accelerators where decode is KV-bandwidth bound — CPU toy
scale is dispatch-bound).

A fourth section drives a **shared-system-prompt workload** through the
prefix cache (repro.serve.prefix) and its cache-off twin: prefix-hit vs
cold token parity and the refcount invariant are asserted, and the
section records hit rate, prefill tokens computed per request (>= 2x
reduction asserted) and TTFT p50/p95 split hot vs cold.

A ``learned_policy`` section closes the loop on the paper's RL agent
against serving traffic: the deterministic workload suite
(repro.serve.workloads) is served under the adaptive heuristic with the
trace recorder on, repro.train.serve_policy trains the policy net
offline on that trace, and the suite is replayed with ``mode="learned"``
— the Eq. 13 reward gain over the heuristic (at equal-or-lower mean kept
rank) and the replay validity land in the JSON for check_bench to gate.

    PYTHONPATH=src python -m benchmarks.serve_bench [--smoke]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import subprocess
import sys
import time

import numpy as np


def compile_guard() -> dict:
    """Runtime sanitizer lane (repro.analysis.sanitizer) in a fresh
    subprocess: transfer-guarded fused steps plus warm/steady compile
    counts.  A subprocess because compile counting must start from an
    empty executable cache.

    ``main()`` runs it before this process imports JAX, and the child
    inherits the platform choice of the environment: on a machine with
    a chip the child holds it alone and exits before the parent starts.
    The section records the platform the child ran on.

    The counts are deterministic (same engines, same shape layout every
    run), so check_bench gates them exactly: steady_new_executables
    must be 0 and warm_executables must not grow past the committed
    baseline."""
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis.sanitizer", "--json"],
        capture_output=True, text=True)
    try:
        doc = json.loads(proc.stdout)
    except json.JSONDecodeError:
        return {"ok": False,
                "error": (proc.stderr or proc.stdout)[-2000:]}
    out = {"ok": doc["ok"], "platform": doc["platform"]}
    for res in doc["scenarios"]:
        out[res["scenario"]] = {
            k: res.get(k) for k in ("warm_executables",
                                    "steady_new_executables",
                                    "transfer_guard", "ok", "error")
            if k in res}
    return out


def obs_overhead(cfg, params, workload, n_slots: int, max_len: int):
    """Observability overhead, report-only: the identical workload with
    span/phase tracing OFF (metrics registry still on — it always is)
    vs ON. Token parity is asserted; tok/s both ways and the ratio are
    recorded as ``info`` rows so drift is visible in review without
    gating CI on sub-millisecond host timing noise."""
    from repro.serve import EngineConfig, Request, ServeEngine

    def drive(obs_trace):
        eng = ServeEngine(cfg, params, config=EngineConfig(
            n_slots=n_slots, max_len=max_len, page_size=16, segment_len=8,
            max_new_cap=max(w["max_new"] for w in workload), prefill_chunk=8,
            obs_trace=obs_trace))
        for w in workload:
            eng.submit(Request(**w))
        eng.warmup()
        outs = eng.run()
        tok_s = eng.stats["tokens_decoded"] / max(eng.stats["decode_s"], 1e-9)
        return outs, tok_s, eng

    outs_off, tok_off, _ = drive(False)
    outs_on, tok_on, eng_on = drive(True)
    parity = all(np.array_equal(outs_off[w["rid"]], outs_on[w["rid"]])
                 for w in workload)
    assert parity, "token streams diverged with obs tracing enabled"
    doc = eng_on.obs.chrome_trace()
    snap = eng_on.obs.snapshot()
    return {
        "parity": parity,
        "tok_per_s_off": tok_off,
        "tok_per_s_on": tok_on,
        "on_off_ratio": tok_on / max(tok_off, 1e-9),
        "trace_events": len(doc["traceEvents"]),
        "trace_dropped": doc["otherData"]["dropped_events"],
        "ttft_count": snap["metrics"]["serve.ttft_s"]["count"],
    }


def build_workload(n_requests: int, max_new: int, seed: int = 0):
    """Mixed prompt lengths (8..32), arrivals staggered every 2 steps."""
    rnd = np.random.default_rng(seed)
    lens = rnd.choice([8, 12, 16, 24, 32], size=n_requests)
    return [dict(rid=i, tokens=rnd.integers(0, 256, int(s)).astype(np.int32),
                 max_new=max_new, arrival=2 * i)
            for i, s in enumerate(lens)]


def factor_compare(cfg, params, workload, n_slots: int, max_len: int):
    """Factored vs dense paged decode.

    Runs the same workload through four engines: a full-rank pair whose
    token outputs must be IDENTICAL (the factor path changes the memory
    layout, not the math), and a low-rank pair (grid top = dh/2) whose
    score-contraction read-bytes-per-token quantify the r/d bandwidth cut.
    """
    from repro.configs.base import RankConfig
    from repro.serve import EngineConfig, Request, ServeEngine

    dh = cfg.resolved_head_dim()
    hkv = cfg.num_kv_heads

    def drive(rank_cfg, factor):
        ecfg = EngineConfig(n_slots=n_slots, max_len=max_len, page_size=16,
                            segment_len=8,
                            max_new_cap=max(w["max_new"] for w in workload),
                            factor_cache=factor)
        eng = ServeEngine(cfg.with_(rank=rank_cfg), params, config=ecfg)
        for w in workload:
            eng.submit(Request(**w))
        eng.warmup()
        outs = eng.run()
        c = eng.cache
        width = c.r_keep if factor else dh
        itemsize = np.dtype(np.asarray(c.k_pool).dtype).itemsize
        # score-contraction K-side read per decoded token: one gather of
        # the slot's logical view (pages * page_size positions) per layer
        read = cfg.num_layers * c.max_len * hkv * width * itemsize
        return outs, {
            "tok_per_s": eng.stats["tokens_decoded"]
                         / max(eng.stats["decode_s"], 1e-9),
            "k_read_bytes_per_token": read,
        }

    full = RankConfig(mode="fixed", rank_grid=(dh // 2, dh), fixed_rank=dh,
                      segment_len=8)
    outs_f, stats_f = drive(full, True)
    outs_d, stats_d = drive(full, False)
    parity = all(np.array_equal(outs_f[w["rid"]], outs_d[w["rid"]])
                 for w in workload)
    assert parity, "factored decode diverged from dense paged decode " \
                   "at full rank"

    low = RankConfig(mode="adaptive", rank_grid=(dh // 4, dh // 2),
                     segment_len=8)
    _, lo_f = drive(low, True)
    _, lo_d = drive(low, False)
    return {
        "parity_full_rank": parity,
        "full_rank": {"factored": stats_f, "dense": stats_d},
        "low_rank": {"factored": lo_f, "dense": lo_d,
                     "r_keep": dh // 2, "dh": dh,
                     "read_ratio": lo_f["k_read_bytes_per_token"]
                                   / lo_d["k_read_bytes_per_token"]},
    }


def prefix_compare(cfg, params, n_slots: int, max_len: int,
                   smoke: bool = False):
    """Shared-system-prompt traffic with the prefix cache on vs off.

    Every request = one shared 32-token prefix + a unique 8-token tail,
    arrivals spaced so the first prefill finishes (and inserts into the
    radix tree) before the rest arrive. Token parity between the two
    engines is asserted (prefix-hit admission must equal cold admission),
    the refcount invariant is checked after the run, and the JSON section
    records hit rate, prefill tokens computed per request (the >= 2x
    reduction headline) and TTFT p50/p95 split hot (prefix hit) vs cold.
    """
    from repro.serve import EngineConfig, Request, ServeEngine

    rnd = np.random.default_rng(7)
    n_req, shared_len, tail, max_new = (4 if smoke else 8), 32, 8, 8
    shared = rnd.integers(0, 256, shared_len).astype(np.int32)
    reqs = [dict(rid=i,
                 tokens=np.concatenate(
                     [shared, rnd.integers(0, 256, tail).astype(np.int32)]),
                 max_new=max_new, arrival=6 * i)
            for i in range(n_req)]

    def drive(prefix):
        # time_per_token blocks every fused step, so TTFT is a true wall
        # (free-running dispatch would timestamp the enqueue, not the
        # token)
        eng = ServeEngine(cfg, params, config=EngineConfig(
            n_slots=n_slots, max_len=max_len, page_size=16, segment_len=8,
            max_new_cap=max_new, prefill_chunk=16, prefix_cache=prefix,
            time_per_token=True))
        # two passes: the first also compiles the admission-time control
        # ops (snapshot slices, mass rehydration, CoW copies) that
        # warmup() cannot reach; the second pass is the measurement —
        # reset() clears the tree, so its hit pattern is identical
        for rep in range(2):
            if rep:
                eng.reset()
            for w in reqs:
                eng.submit(Request(**w))
            eng.warmup()
            outs = eng.run()
        s = eng.stats
        # first_token_s and sched.finished append in the same eviction
        # loop, so they zip rid-aligned
        pairs = [(req.rid, t * 1e3) for (req, _), t
                 in zip(eng.sched.finished, eng.first_token_s)]
        hot = [t for rid, t in pairs if eng.request_prefix_hit.get(rid)]
        cold = [t for rid, t in pairs if not eng.request_prefix_hit.get(rid)]
        if prefix:
            eng.cache.check_refs(eng.prefix.all_pages())
        def pct(xs):
            return (None if not xs else
                    {"p50_ms": float(np.percentile(xs, 50)),
                     "p95_ms": float(np.percentile(xs, 95))})
        return outs, {
            "hit_rate": s["prefix_hits"] / n_req if prefix else 0.0,
            "reused_tokens": s["prefix_reused_tokens"],
            "prefill_tokens_per_request": s["prefill_tokens"] / n_req,
            "cow_pages": s["prefix_cow"],
            "evicted_pages": s["prefix_evictions"],
            "ttft_hot": pct(hot),
            "ttft_cold": pct(cold),
            "tok_per_s": s["tokens_decoded"] / max(s["decode_s"], 1e-9),
        }

    outs_on, on = drive(True)
    outs_off, off = drive(False)
    parity = all(np.array_equal(outs_on[w["rid"]], outs_off[w["rid"]])
                 for w in reqs)
    assert parity, "prefix-hit admission diverged from cold admission"
    reduction = (off["prefill_tokens_per_request"]
                 / max(on["prefill_tokens_per_request"], 1e-9))
    assert reduction >= 2.0, \
        f"prefill-token reduction {reduction:.2f}x below the 2x bar"
    return {
        "parity": parity,
        "workload": {"n_requests": n_req, "shared_len": shared_len,
                     "tail_len": tail},
        "cached": on,
        "baseline": off,
        "prefill_token_reduction": reduction,
    }


def spec_compare(cfg, params, workload, n_slots: int, max_len: int,
                 repeats: int = 2, draft_k: int = 7,
                 draft_rank_frac: float = 0.25):
    """Low-rank self-speculative decode vs plain chunked decode.

    Both engines run the identical workload; token parity is asserted
    (speculation is exact — it may only change speed). Records the draft
    accept rate (accepted / draftable), the mean accepted run length per
    fused step (1 = all drafts rejected .. draft_k + 1 = all survived),
    and the tok/s ratio. Both engines are built first, then measurement
    reps ALTERNATE plain/spec (best-of-``repeats`` each): host timing
    drifts across a process's lifetime, and back-to-back blocks would
    hand one engine a systematically warmer machine than the other.

    ``draft_k`` defaults to segment_len - 1: accepts are clamped at
    segment boundaries anyway (rank decisions must fire at identical
    token counts), so a segment-aligned draft window is the largest one
    that can fully accept — a perfect run covers a whole segment in one
    fused dispatch. ``draft_rank_frac`` defaults to r/4 — the policy
    floor clamps the draft rank from below, so quarter-rank drafts
    accept just as often as half-rank ones while reading less.

    The workload's decode budget is raised to 32 tokens per request:
    speculation targets the decode phase, and the smoke workload's
    8-token windows are over in a handful of steps — all prefill,
    admission and decision overhead, which both engines pay identically,
    drowning the signal in dispatch noise.

    What to gate: accept rate and tokens-per-dispatch (mean accepted run
    length) are deterministic given the model and workload. The
    wall-clock tok/s ratio is NOT a meaningful gate at this scale — the
    draft's rank cut saves attention/KV reads, which are negligible for
    a toy model at seq <= 80 on CPU, so a quarter-rank draft forward
    costs about the same as the full fused step it replaces and the
    measured ratio sits near or below 1.0. The speedup this subsystem
    buys is per-dispatch: ~6x fewer fused steps (and host syncs) per
    decoded token, which converts to wall-clock exactly where decode is
    dispatch- or KV-read-bound."""
    from repro.serve import EngineConfig, Request, ServeEngine

    workload = [dict(w, max_new=32) for w in workload]
    max_len = max(max_len, 32 + 32 + 16)  # longest prompt + budget + slack

    def build(speculative):
        return ServeEngine(cfg, params, config=EngineConfig(
            n_slots=n_slots, max_len=max_len, page_size=16, segment_len=8,
            max_new_cap=max(w["max_new"] for w in workload), prefill_chunk=8,
            speculative=speculative, draft_k=draft_k,
            draft_rank_frac=draft_rank_frac))

    def pass_(eng, warmed):
        if warmed:
            eng.reset()
        for w in workload:
            eng.submit(Request(**w))
        if not warmed:
            eng.warmup()
        outs = eng.run()
        return outs, dict(eng.stats)

    engines = {False: build(False), True: build(True)}
    best = {False: None, True: None}
    for rep in range(max(repeats, 2) + 1):
        for speculative in (False, True):
            outs, st = pass_(engines[speculative], warmed=rep > 0)
            if rep == 0:
                continue  # warm pass: compiles + control-plane one-offs
            if (best[speculative] is None
                    or st["decode_s"] < best[speculative][1]["decode_s"]):
                best[speculative] = (outs, st)

    outs_p, sp = best[False]
    outs_s, ss = best[True]
    parity = all(np.array_equal(outs_p[w["rid"]], outs_s[w["rid"]])
                 for w in workload)
    assert parity, "speculative decode diverged from plain decode"
    tok_plain = sp["tokens_decoded"] / max(sp["decode_s"], 1e-9)
    tok_spec = ss["tokens_decoded"] / max(ss["decode_s"], 1e-9)
    return {
        "parity": parity,
        "draft_k": draft_k,
        "draft_rank_frac": draft_rank_frac,
        "accept_rate": ss["spec_accepted"] / max(ss["spec_drafted"], 1),
        # accepted run per stream-step: each decoding row contributes one
        # bonus token per step, so row-steps == spec_tokens - spec_accepted
        "mean_accept_len": ss["spec_tokens"]
                           / max(ss["spec_tokens"] - ss["spec_accepted"], 1),
        "spec_steps": ss["spec_steps"],
        "steps_plain": sp["steps"],
        "tok_per_s": tok_spec,
        "tok_per_s_plain": tok_plain,
        "tok_per_s_ratio": tok_spec / max(tok_plain, 1e-9),
    }


def learned_policy_compare(cfg, params, smoke: bool = False,
                           work_dir: str | None = None):
    """Close the loop on the paper's RL agent against serving traffic:
    record traces -> train offline -> replay with ``mode="learned"``.

    1. The deterministic workload suite (repro.serve.workloads) is served
       under the adaptive heuristic with the trace recorder attached —
       one shared recorder across all scenarios, one dataset out.
    2. repro.train.serve_policy trains the policy net on that trace
       (BC warm start -> constrained-oracle BC -> PPO) and the offline
       replay evaluation scores learned vs adaptive (the recorded
       actions) vs the constrained oracle on the same Eq. 13 reward.
    3. The suite is served again with ``mode="learned"`` — stream
       validity is asserted, and a second trace records the ranks the
       learned policy actually kept.

    What to gate: ``reward_gain`` (learned minus adaptive Eq. 13 reward,
    must not be negative — the constrained oracle dominates the
    heuristic by construction, so a trained policy that loses reward
    has failed to fit) and ``rank_ratio`` (learned/adaptive mean kept
    rank, must stay <= 1: the policy may not buy reward with extra
    factor-read bytes). Both are deterministic given model + workloads.
    ``replay.serve_rank_ratio`` is informational — at serve time the
    policy feeds back into its own prev-rank state, so its trajectory
    legitimately drifts from the offline replay."""
    import tempfile

    from repro.configs.base import RankConfig
    from repro.serve import EngineConfig, Request, ServeEngine
    from repro.serve.traces import TraceReader, TraceRecorder
    from repro.serve.workloads import build, make_workload, workload_names
    from repro.train.serve_policy import load_policy, train_serve_policy

    n_requests, max_new = (4, 10) if smoke else (8, 24)
    grid = (4, 8, 12, 16)
    acfg = cfg.with_(rank=RankConfig(mode="adaptive", rank_grid=grid,
                                     segment_len=8))
    lcfg = cfg.with_(rank=RankConfig(mode="learned", rank_grid=grid,
                                     segment_len=8))
    specs = [make_workload(n, seed=3, n_requests=n_requests,
                           max_new=max_new, vocab=cfg.vocab_size,
                           max_prompt=40) for n in workload_names()]

    def serve_suite(run_cfg, policy_params, recorder):
        served = 0
        valid = True
        for spec in specs:
            ecfg = EngineConfig(n_slots=4, max_len=96, page_size=16,
                                segment_len=8, max_new_cap=max_new,
                                prefill_chunk=8, record_traces=recorder,
                                **spec.engine_overrides)
            eng = ServeEngine(run_cfg, params, policy_params, config=ecfg)
            for r in build(spec):
                eng.submit(r)
            outs = eng.run()
            served += len(outs)
            valid = valid and all(
                0 < len(v) <= max_new for v in outs.values())
        recorder.flush()
        return served, valid

    with tempfile.TemporaryDirectory() as tmp:
        base = work_dir or tmp
        adir, ldir, pdir = (f"{base}/trace_adaptive", f"{base}/trace_learned",
                            f"{base}/policy")
        _, a_valid = serve_suite(
            acfg, None, TraceRecorder(adir, acfg, scenario="suite"))
        _, history = train_serve_policy(
            adir, acfg.rank, out_dir=pdir,
            bc_steps=40 if smoke else 160,
            ppo_steps=2 if smoke else 8)
        pol = load_policy(pdir)
        served, l_valid = serve_suite(
            lcfg, pol, TraceRecorder(ldir, lcfg, scenario="suite"))
        rank_adaptive = float(
            np.mean(TraceReader(adir).records["chosen_rank"]))
        rank_learned = float(
            np.mean(TraceReader(ldir).records["chosen_rank"]))

    ev = history["eval"]
    return {
        "workloads": workload_names(),
        "n_requests": n_requests, "max_new": max_new,
        "n_records": ev["n_records"],
        "offline": {k: ev[k] for k in ("learned", "adaptive", "oracle")},
        "picked": ev["picked"],
        "reward_gain": ev["learned"]["reward"] - ev["adaptive"]["reward"],
        "rank_ratio": ev["learned"]["mean_rank"]
                      / max(ev["adaptive"]["mean_rank"], 1e-9),
        "agreement_gain": ev["learned"]["agreement"]
                          - ev["adaptive"]["agreement"],
        "replay": {
            "served_requests": served,
            "valid": bool(a_valid and l_valid),
            "mean_rank_adaptive": rank_adaptive,
            "mean_rank_learned": rank_learned,
            "serve_rank_ratio": rank_learned / max(rank_adaptive, 1e-9),
        },
    }


def router_compare(cfg, params, smoke: bool = False):
    """Multi-replica front door: prefix-affinity routing vs round-robin
    vs a single replica.

    Workload: ``n_groups`` distinct shared prefixes (system prompts),
    each fanned out to ``per_group`` requests with unique tails. Group
    leaders go first and finish (warming exactly one replica's radix
    tree per group), then the remaining traffic arrives as one burst.
    The full-mode group count is sized so all chains together OVERFLOW
    one replica's page pool but two groups per replica fit: affinity
    routing partitions groups across the fleet (aggregate cache
    capacity), while a single replica — and round-robin, which sprays
    every group onto every replica — LRU-evicts shared chains and
    re-prefills. That cut prefill work is what makes the 2-replica
    fleet beat one replica wall-clock even on a single-core host.
    """
    from repro.serve import EngineConfig, FleetConfig, Router, SamplingParams

    rnd = np.random.default_rng(11)
    n_groups, per_group = (2 if smoke else 4), 3
    shared_len, tail, max_new = 48, 8, (6 if smoke else 10)
    groups = [rnd.integers(0, 256, shared_len).astype(np.int32)
              for _ in range(n_groups)]
    tails = [[rnd.integers(0, 256, tail).astype(np.int32)
              for _ in range(per_group)] for _ in groups]
    # burst arrival order — smoke: each group's follow-ups back to back,
    # which provably misaligns a 2-replica round-robin rotation (a
    # consecutive pair always straddles both replicas, so every group
    # cold-misses somewhere); full: a fixed-seed shuffle of the
    # 4-group burst, so round-robin sprays groups across replicas
    # while affinity re-partitions them
    order = [(g, j) for g in range(n_groups) for j in range(1, per_group)]
    if not smoke:
        order = [order[k] for k in rnd.permutation(len(order))]
    # 48-token prefixes = 3-page chains; prefix_pages=2 -> 12 usable
    # pages per replica: 2 chains stay resident, 4 can't (the overflow
    # described above), and every miss re-pays 3 prefill chunks
    ecfg = EngineConfig(n_slots=2, max_len=80, page_size=16, segment_len=8,
                        max_new_cap=max_new, prefill_chunk=16,
                        prefix_cache=True, prefix_pages=2, sampling=False)

    def drive(routing, n_replicas, repeats=2 if smoke else 5):
        # best-of-N per side: the decode window is ~0.1 s at this scale
        # and stepping threads add scheduler jitter
        best = None
        fleet = FleetConfig(engine=ecfg, n_replicas=n_replicas,
                            routing=routing, affinity_min_tokens=16,
                            idle_poll_s=0.002)
        router = Router(cfg, params, fleet=fleet)
        sp = SamplingParams(max_new=max_new)
        for _ in range(repeats):
            router.reset()
            t0 = time.perf_counter()
            leaders = [router.submit(np.concatenate([g, t[0]]), sp)
                       for g, t in zip(groups, tails)]
            for h in leaders:
                h.result()        # warm one replica per group
            burst = [router.submit(np.concatenate([groups[g], tails[g][j]]),
                                   sp) for g, j in order]
            router.drain()
            wall = time.perf_counter() - t0
            st = router.stats()
            res = {
                "hit_rate": st["aggregate"]["hit_rate"],
                "tokens": st["aggregate"]["tokens_decoded"],
                "tok_per_s": st["aggregate"]["tokens_decoded"] / wall,
                "wall_s": wall,
                "prefill_tokens": sum(p["engine"]["prefill_tokens"]
                                      for p in st["replicas"]),
                "routed": st["routed"],
                "route_kinds": st["route_kinds"],
                "burst_replicas": sorted({h.replica for h in burst}),
            }
            if best is None or res["tok_per_s"] > best["tok_per_s"]:
                best = res
        router.shutdown()
        return best

    aff = drive("affinity", 2)
    rr = drive("round_robin", 2)
    single = drive("affinity", 1)
    assert aff["hit_rate"] > rr["hit_rate"], \
        f"affinity hit-rate {aff['hit_rate']:.2f} not above round-robin " \
        f"{rr['hit_rate']:.2f}"
    ratio = aff["tok_per_s"] / max(single["tok_per_s"], 1e-9)
    if not smoke:
        # one replica = same total compute on this host; the fleet must
        # at least hold parity while doubling the lanes in flight
        assert ratio >= 1.0, \
            f"2-replica aggregate {aff['tok_per_s']:.0f} tok/s below " \
            f"single replica {single['tok_per_s']:.0f} tok/s"
    return {
        "workload": {"n_groups": n_groups, "per_group": per_group,
                     "shared_len": shared_len, "tail_len": tail,
                     "max_new": max_new},
        "n_replicas": 2,
        "affinity": aff,
        "round_robin": rr,
        "single": single,
        "hit_rate_gain": aff["hit_rate"] - rr["hit_rate"],
        "tok_per_s_ratio_vs_single": ratio,
    }


def run(quick: bool = False, smoke: bool = False, n_slots: int = 8,
        out_path: str = "BENCH_serve.json", guard: dict | None = None):
    """All sections in this process. ``guard`` is the compile_guard()
    result, which has to be taken before this process touches JAX
    (``main()`` does); without it the section is left out."""
    import jax

    from repro.configs import get_config
    from repro.configs.base import RankConfig
    from repro.models.api import get_model
    from repro.serve import EngineConfig, Request, ServeEngine
    from repro.serve.api import Engine, SamplingParams

    n_requests, max_new = (4, 8) if smoke else (8, 16) if quick else (16, 24)
    if smoke:
        n_slots = min(n_slots, 4)
    cfg = get_config("drrl-paper", reduced=True).with_(
        rank=RankConfig(mode="adaptive", rank_grid=(4, 8, 12, 16),
                        segment_len=8))
    fns = get_model(cfg)
    params = fns.init(jax.random.PRNGKey(0))
    workload = build_workload(n_requests, max_new)
    max_len = 64

    repeats = 1 if smoke else 2

    # -- multi-replica router: affinity vs round-robin vs 1 replica -----
    # first, while the process is clean: the fleet-vs-single wall-clock
    # comparison is sensitive to heap size and stray live engines from
    # the other sections (its bands were calibrated in a fresh process)
    router_res = router_compare(cfg, params, smoke=smoke)

    # -- continuous batching --------------------------------------------
    # throughput runs: free-running dispatch (no per-step blocking);
    # best-of-N because the decode window is sub-second at this scale
    ecfg = EngineConfig(n_slots=n_slots, max_len=max_len, page_size=16,
                        segment_len=8, max_new_cap=max_new)
    eng = ServeEngine(cfg, params, config=ecfg)
    es = None
    compile_s = 0.0
    for rep in range(repeats):
        if rep:
            eng.reset()
        for w in workload:
            eng.submit(Request(**w))
        eng.warmup()
        eng.run()
        compile_s += eng.stats["compile_s"]
        if es is None or eng.stats["decode_s"] < es["decode_s"]:
            es = dict(eng.stats)
    es["compile_s"] = compile_s
    # latency run: same workload, blocking each fused step for honest
    # per-token wall times (the blocking itself costs throughput, so the
    # two metrics come from separate runs over identical requests)
    eng = ServeEngine(cfg, params,
                      config=dataclasses.replace(ecfg, time_per_token=True))
    for w in workload:
        eng.submit(Request(**w))
    eng.warmup()
    eng.run()
    lat = np.asarray(eng.token_latencies) * 1e3        # ms per decoded token
    engine_res = {
        "tok_per_s": es["tokens_decoded"] / max(es["decode_s"], 1e-9),
        "p50_ms": float(np.percentile(lat, 50)) if lat.size else None,
        "p95_ms": float(np.percentile(lat, 95)) if lat.size else None,
        "first_token_s_mean": float(np.mean(eng.first_token_s))
                              if eng.first_token_s else None,
        "decode_s": es["decode_s"],
        "compile_s": es["compile_s"], "steps": es["steps"],
        "tokens_decoded": es["tokens_decoded"], "n_slots": n_slots,
    }

    # -- sequential per-request lock-step (1-slot api.Engine) -----------
    def seq_engine(timed: bool) -> Engine:
        return Engine(cfg, params, config=dataclasses.replace(
            ecfg, n_slots=1, time_per_token=timed))

    seq_server = seq_engine(False)
    best = None
    for _ in range(repeats):
        seq_decode_s = seq_compile_s = 0.0
        seq_tokens = 0
        for w in workload:
            seq_server.reset()
            seq_server.submit(w["tokens"],
                              SamplingParams(max_new=w["max_new"]))
            seq_compile_s += seq_server.warmup()
            seq_server.run()
            s = seq_server.stats
            seq_decode_s += s["decode_s"]
            seq_tokens += s["tokens_decoded"]
        if best is None or seq_decode_s < best[0]:
            best = (seq_decode_s, seq_compile_s, seq_tokens)
    seq_decode_s, seq_compile_s, seq_tokens = best
    # sequential latency pass: same per-step blocking the engine's latency
    # run uses, so both p50/p95 are true per-token walls
    seq_lat = []
    server_lat = seq_engine(True)
    for w in workload:
        server_lat.reset()
        server_lat.submit(w["tokens"], SamplingParams(max_new=w["max_new"]))
        server_lat.warmup()
        server_lat.run()
        seq_lat.extend(t * 1e3 for t in server_lat.core.token_latencies)
    seq_lat = np.asarray(seq_lat)
    seq_res = {
        "tok_per_s": seq_tokens / max(seq_decode_s, 1e-9),
        "p50_ms": float(np.percentile(seq_lat, 50)) if seq_lat.size else None,
        "p95_ms": float(np.percentile(seq_lat, 95)) if seq_lat.size else None,
        "decode_s": seq_decode_s,
        "compile_s": seq_compile_s, "tokens_decoded": seq_tokens,
    }

    # -- factor-form cache: parity + read bandwidth ---------------------
    fc_workload = workload[:4] if not smoke else workload
    factor_res = factor_compare(cfg, params, fc_workload,
                                n_slots=min(n_slots, 4), max_len=max_len)

    # -- shared-prefix KV reuse: hit rate, prefill cut, hot/cold TTFT ---
    prefix_res = prefix_compare(cfg, params, n_slots=min(n_slots, 4),
                                max_len=max_len, smoke=smoke)

    # -- self-speculative decode: accept rate + tok/s vs plain ----------
    # spec_compare runs its own warm pass (the plain engine pays a
    # one-off mid-run compile there) and alternates plain/spec
    # measurement reps so host-timing drift cancels out of the ratio
    spec_res = spec_compare(cfg, params, workload,
                            n_slots=min(n_slots, 4), max_len=max_len,
                            repeats=max(repeats, 2))

    # -- observability overhead: tracing on vs off, parity asserted -----
    obs_res = obs_overhead(cfg, params, workload, n_slots=min(n_slots, 4),
                           max_len=max_len)

    # -- learned rank policy: trace -> offline train -> replay ----------
    learned_res = learned_policy_compare(cfg, params, smoke=smoke)

    out = {
        "workload": {"n_requests": n_requests, "max_new": max_new,
                     "prompt_lens": [len(w["tokens"]) for w in workload],
                     "arrivals": [w["arrival"] for w in workload]},
        "engine": engine_res,
        "sequential": seq_res,
        "speedup": engine_res["tok_per_s"] / max(seq_res["tok_per_s"], 1e-9),
        "factor_cache": factor_res,
        "prefix_cache": prefix_res,
        "speculative": spec_res,
        "router": router_res,
        "obs": obs_res,
        "learned_policy": learned_res,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    if guard is not None:
        out["compile_guard"] = guard
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload — CI canary")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args()
    # runtime sanitizer: transfer guard + steady-state compile count, in
    # a child that holds the device alone, before this process imports JAX
    guard = compile_guard()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    res = run(quick=args.quick, smoke=args.smoke, n_slots=args.slots,
              out_path=args.out, guard=guard)
    e, s = res["engine"], res["sequential"]
    print(f"engine     : {e['tok_per_s']:8.1f} tok/s  "
          f"p50 {e['p50_ms']:.1f} ms  p95 {e['p95_ms']:.1f} ms  "
          f"(compile {e['compile_s']:.2f}s excluded)")
    print(f"sequential : {s['tok_per_s']:8.1f} tok/s  "
          f"p50 {s['p50_ms']:.1f} ms  p95 {s['p95_ms']:.1f} ms")
    print(f"speedup    : {res['speedup']:.2f}x  -> {args.out}")
    fc = res["factor_cache"]
    lo = fc["low_rank"]
    print(f"factor     : parity@full-rank {fc['parity_full_rank']}  "
          f"K-read/token {lo['factored']['k_read_bytes_per_token']}B vs "
          f"{lo['dense']['k_read_bytes_per_token']}B dense "
          f"(ratio {lo['read_ratio']:.2f} = r{lo['r_keep']}/d{lo['dh']})")
    px = res["prefix_cache"]
    hot = px["cached"]["ttft_hot"] or {"p50_ms": float("nan")}
    cold = px["baseline"]["ttft_cold"]
    print(f"prefix     : parity {px['parity']}  hit rate "
          f"{px['cached']['hit_rate']:.2f}  prefill tok/req "
          f"{px['cached']['prefill_tokens_per_request']:.1f} vs "
          f"{px['baseline']['prefill_tokens_per_request']:.1f} "
          f"({px['prefill_token_reduction']:.1f}x cut); TTFT p50 "
          f"{hot['p50_ms']:.1f} ms hot vs {cold['p50_ms']:.1f} ms cold")
    sd = res["speculative"]
    print(f"speculative: parity {sd['parity']}  accept rate "
          f"{sd['accept_rate']:.2f}  mean run {sd['mean_accept_len']:.2f} "
          f"tok/step (draft_k {sd['draft_k']}); "
          f"{sd['tok_per_s']:.0f} tok/s vs {sd['tok_per_s_plain']:.0f} "
          f"plain (ratio {sd['tok_per_s_ratio']:.2f})")
    rt = res["router"]
    print(f"router     : hit rate {rt['affinity']['hit_rate']:.2f} affinity "
          f"vs {rt['round_robin']['hit_rate']:.2f} round-robin; "
          f"2-replica {rt['affinity']['tok_per_s']:.0f} tok/s vs "
          f"1-replica {rt['single']['tok_per_s']:.0f} tok/s "
          f"(ratio {rt['tok_per_s_ratio_vs_single']:.2f})")
    ob = res["obs"]
    print(f"obs        : parity {ob['parity']}  tok/s on/off ratio "
          f"{ob['on_off_ratio']:.2f} ({ob['tok_per_s_on']:.0f} traced vs "
          f"{ob['tok_per_s_off']:.0f} plain); {ob['trace_events']} trace "
          f"events, {ob['trace_dropped']} dropped")
    lp = res["learned_policy"]
    print(f"learned    : replay valid {lp['replay']['valid']}  reward "
          f"{lp['offline']['learned']['reward']:.4f} vs "
          f"{lp['offline']['adaptive']['reward']:.4f} adaptive "
          f"(gain {lp['reward_gain']:+.4f}); mean rank "
          f"{lp['offline']['learned']['mean_rank']:.2f} vs "
          f"{lp['offline']['adaptive']['mean_rank']:.2f} "
          f"(ratio {lp['rank_ratio']:.3f}, {lp['n_records']} records)")
    cg = res["compile_guard"]
    if cg.get("error"):
        print(f"sanitizer  : FAILED — {cg['error'][:200]}")
    else:
        ms, sp = cg["mixed_sampling"], cg["speculative"]
        lg = cg.get("learned_policy", {})
        og = cg.get("observability", {})
        print(f"sanitizer  : {'ok' if cg['ok'] else 'FAIL'}  "
              f"transfer guard disallow; executables warm/steady "
              f"{ms['warm_executables']}/+{ms['steady_new_executables']} "
              f"mixed, {sp['warm_executables']}/+"
              f"{sp['steady_new_executables']} speculative, "
              f"{lg.get('warm_executables', '?')}/+"
              f"{lg.get('steady_new_executables', '?')} learned, "
              f"{og.get('warm_executables', '?')}/+"
              f"{og.get('steady_new_executables', '?')} obs")
    if res["speedup"] <= 1.0 and not args.smoke:
        # --smoke is a does-it-run canary: 4 under-saturated requests,
        # single repeat — not a throughput measurement
        print("WARNING: continuous batching did not beat sequential decode")


if __name__ == "__main__":
    main()
