#!/usr/bin/env python3
"""Serve the full-width drrl-paper model on a TPU through the normal front
door, and check what comes out.

    python chip_smoke.py              # phases (a)-(d) on one chip
    python chip_smoke.py --chips 4    # Router phase only: 4 replicas vs 1

The model is ``get_config("drrl-paper")`` at its published widths (12
layers, d_model 768, 12 heads of 64, vocab 50257, f32) with random
weights and a random DR-RL agent from fixed seeds. Every request goes in
through ``repro.serve.api.Engine`` wrapped in a
``repro.serve.frontend.FrontEnd`` (or a ``Router`` of them) and comes
back through its ``RequestHandle``. One engine per phase:

  (a) ``EngineConfig`` defaults, rank mode drrl: the XLA attention path;
  (b) the same traffic with ``use_kernel=True``: the compiled fused steps
      must contain the Pallas kernel (``tpu_custom_call``);
  (c) ``prefix_cache`` + ``speculative`` over shared-prefix prompts;
  (d) rank mode off, greedy: every emitted token is checked against a
      float32 ``forward_dense`` at highest matmul precision over prompt
      plus output. A token passes when the reference's top logit exceeds
      its logit for that token by at most ``LOGIT_TOL``.

(a)-(c) together run every executable that donates its buffers:
``_step``, ``_step_mixed``, ``_step_spec`` and ``decide``.

Each phase prints one JSON line (a smoke record, not a benchmark); the
last line is ``{"ok": true, "device": {...}}``. With no TPU visible, or
when any phase fails, the script exits non-zero and prints no ok line.
One process drives every chip; it starts no other.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 0
N_REQUESTS = 8
# One fused bf16 pass is the TPU's default precision for f32 matmuls: each
# operand keeps 8 significant bits, so a logit of this model (std ~0.9 at
# random init) moves by about 1e-2 through 12 layers. A token the engine
# picks can then trail the reference's best by twice that; 0.1 leaves a
# 5x margin while a wrong token trails by several logit std.
LOGIT_TOL = 0.1
# traffic: n_slots 8 x max_len 1024; a 64-token prefill chunk is four
# pages; a 16-token segment makes every request (>= 32 new tokens) cross
# at least two rank decisions
ENGINE = dict(n_slots=8, max_len=1024, page_size=16, prefill_chunk=64,
              segment_len=16, max_new_cap=64)


def emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def mixed_traffic(rng, vocab: int, n: int):
    """n (prompt, max_new) pairs: prompts of 64-512 tokens, 32-64 new."""
    return [(rng.integers(0, vocab, int(rng.integers(64, 513)),
                          dtype=np.int32), int(rng.integers(32, 65)))
            for _ in range(n)]


def shared_prefix_traffic(rng, vocab: int, n: int, n_groups: int):
    """n requests in ``n_groups`` groups: a 256-token system prompt per
    group plus a 16-128-token private tail."""
    heads = [rng.integers(0, vocab, 256, dtype=np.int32)
             for _ in range(n_groups)]
    return [(np.concatenate([heads[i % n_groups],
                             rng.integers(0, vocab, int(rng.integers(16, 129)),
                                          dtype=np.int32)]),
             int(rng.integers(32, 65)))
            for i in range(n)]


def serve(front, reqs, n_leaders: int = 0):
    """Submit ``reqs`` through ``front`` (a FrontEnd or Router) and read
    every stream to its end. The first ``n_leaders`` go in alone and the
    rest only once each leader has its first token, so the leaders'
    prompts are in the prefix cache when the followers arrive."""
    from repro.serve.api import SamplingParams
    handles = [front.submit(p, SamplingParams(max_new=m))
               for p, m in reqs[:n_leaders]]
    for h in handles:
        next(iter(h.tokens()))
    handles += [front.submit(p, SamplingParams(max_new=m))
                for p, m in reqs[n_leaders:]]
    outs = [np.fromiter(h.tokens(), np.int32) for h in handles]
    return handles, outs


def check_outputs(reqs, handles, outs, vocab: int) -> None:
    for (_, m), h, o in zip(reqs, handles, outs):
        if not h.done or h.cancelled or len(o) != m:
            raise RuntimeError(f"request {h.rid}: {len(o)}/{m} tokens, "
                               f"done={h.done} cancelled={h.cancelled}")
        if o.min() < 0 or o.max() >= vocab:
            raise RuntimeError(f"request {h.rid}: token out of vocab")


def record(name: str, reqs, handles, outs, stats, **extra) -> None:
    import jax
    ttft = [h.ttft_s for h in handles if h.ttft_s is not None]
    mem = jax.devices()[0].memory_stats() or {}
    rec = {"phase": name, "requests_sent": len(reqs),
           "requests_completed": sum(h.done and not h.cancelled
                                     for h in handles),
           "tokens": int(sum(len(o) for o in outs)),
           "compile_s": stats["compile_s"],
           "ttft_p50_s": float(np.median(ttft)) if ttft else None,
           "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
           "steps": stats["steps"], "mixed_steps": stats["mixed_steps"],
           "spec_steps": stats["spec_steps"], "decides": stats["decides"],
           "prefix_hits": stats["prefix_hits"]}
    rec.update(extra)
    emit(rec)


def fused_step_texts(core) -> dict:
    """Compiled text of the engine's plain and mixed fused steps, lowered
    with the engine's own live state."""
    import jax.numpy as jnp
    c, ns = core.cache, core.n_slots
    args = (core.params, c.k_pool, c.v_pool, c.kt_pool, c.mass_pool,
            core._pt_dev, core.tokens, core._lens_dev, c.ranks, c.basis,
            jnp.zeros((ns,), bool), core.out_buf, core._plen_dev,
            core._temp_dev, core._topk_dev, core._topp_dev, core._seed_dev)
    return {"_step": core._step.lower(*args).compile().as_text(),
            "_step_mixed": core._step_mixed.lower(
                *args, core.prompt_buf).compile().as_text()}


def run_engine(name, cfg, params, policy, reqs, n_leaders=0, **knobs):
    """One phase: a fresh Engine behind a FrontEnd, served to the end."""
    from repro.serve.api import Engine, EngineConfig
    from repro.serve.frontend import FrontEnd
    eng = Engine(cfg, params, policy,
                 config=EngineConfig(**{**ENGINE, **knobs}))
    front = FrontEnd(eng)                   # compiles in warmup()
    try:
        handles, outs = serve(front, reqs, n_leaders)
        if not front.drain(timeout=600):
            raise RuntimeError(f"{name}: engine did not drain")
        with eng._step_lock:
            texts = (fused_step_texts(eng.core) if knobs.get("use_kernel")
                     else None)
    finally:
        front.shutdown()
    check_outputs(reqs, handles, outs, cfg.vocab_size)
    return eng.stats, handles, outs, texts


def phase_default(cfg, params, policy, reqs):
    stats, handles, outs, _ = run_engine("a", cfg, params, policy, reqs)
    n = len(reqs)
    if not (stats["steps"] > stats["mixed_steps"] > 0):
        raise RuntimeError("(a) did not run both _step and _step_mixed")
    if stats["decides"] < 2 * n:
        raise RuntimeError(f"(a) {stats['decides']} rank decisions for "
                           f"{n} requests; expected >= {2 * n}")
    record("a_default_xla", reqs, handles, outs, stats)


def phase_kernel(cfg, params, policy, reqs):
    stats, handles, outs, texts = run_engine("b", cfg, params, policy, reqs,
                                             use_kernel=True)
    missing = [k for k, t in texts.items() if "tpu_custom_call" not in t]
    if missing:
        raise RuntimeError(f"(b) no tpu_custom_call in compiled {missing}: "
                           f"the Pallas kernel was not compiled")
    if stats["decides"] < 2 * len(reqs):
        raise RuntimeError("(b) too few rank decisions")
    record("b_pallas_kernel", reqs, handles, outs, stats,
           kernel_in=sorted(texts))


def phase_prefix_spec(cfg, params, policy, reqs, n_groups):
    # prefix_pages: 128 pages of headroom hold both groups' system prompts
    # and the tails; the default (a second slot-set, 512 pages) would
    # double the pools the speculative step copies per draft forward
    stats, handles, outs, _ = run_engine(
        "c", cfg, params, policy, reqs, n_leaders=n_groups,
        prefix_cache=True, prefix_pages=128, speculative=True)
    if stats["spec_steps"] < 1:
        raise RuntimeError("(c) _step_spec never ran")
    if stats["prefix_hits"] < len(reqs) - n_groups:
        raise RuntimeError(f"(c) {stats['prefix_hits']} prefix hits for "
                           f"{len(reqs) - n_groups} followers")
    if stats["decides"] < 2 * len(reqs):
        raise RuntimeError("(c) too few rank decisions")
    record("c_prefix_speculative", reqs, handles, outs, stats,
           spec_accepted=stats["spec_accepted"],
           spec_drafted=stats["spec_drafted"])


def reference_gaps(cfg, params, reqs, outs):
    """Per emitted token: reference top logit minus the reference logit of
    the emitted token, from one float32 ``forward_dense`` over prompt +
    output at highest matmul precision. Also returns the largest logit
    change between default and highest precision at those positions."""
    import jax
    import jax.numpy as jnp
    from repro.models.transformer import forward_dense
    n, K = len(reqs), max(len(o) for o in outs)
    T = max(len(p) + len(o) - 1 for (p, _), o in zip(reqs, outs))
    T = -(-T // 128) * 128
    toks = np.zeros((n, T), np.int32)
    pos = np.zeros((n, K), np.int32)
    chosen = np.zeros((n, K), np.int32)
    valid = np.zeros((n, K), bool)
    for i, ((p, _), o) in enumerate(zip(reqs, outs)):
        seq = np.concatenate([p, o[:-1]])
        toks[i, :len(seq)] = seq
        pos[i, :len(o)] = np.arange(len(p) - 1, len(p) - 1 + len(o))
        chosen[i, :len(o)] = o
        valid[i, :len(o)] = True

    def rows(params, toks, pos):
        lg = forward_dense(cfg, params, toks)[0]             # (n, T, V)
        return jnp.take_along_axis(lg, pos[:, :, None], axis=1)

    def gaps(params, toks, pos, chosen):
        r = rows(params, toks, pos)                           # (n, K, V)
        pick = jnp.take_along_axis(r, chosen[:, :, None], axis=2)[..., 0]
        return r.max(-1) - pick, r, r.std(-1)

    with jax.default_matmul_precision("highest"):
        gap, r_hi, sd = jax.jit(gaps)(params, toks, pos, chosen)
    r_lo = jax.jit(rows)(params, toks, pos)
    noise = jnp.max(jnp.abs(r_lo - r_hi), axis=-1)
    gap, sd, noise = (np.asarray(x)[valid] for x in (gap, sd, noise))
    return gap, sd, noise


def phase_reference(cfg, params, reqs):
    from repro.configs.base import RankConfig
    cfg_off = cfg.with_(rank=RankConfig(mode="off"))
    stats, handles, outs, _ = run_engine("d", cfg_off, params, None, reqs)
    gap, sd, noise = reference_gaps(cfg_off, params, reqs, outs)
    record("d_reference_off", reqs, handles, outs, stats,
           logit_tol=LOGIT_TOL, max_gap=float(gap.max()),
           tokens_not_ref_argmax=int((gap > 0).sum()),
           logit_std_median=float(np.median(sd)),
           default_vs_highest_max=float(noise.max()))
    if gap.max() > LOGIT_TOL:
        raise RuntimeError(f"(d) emitted token trails the reference top "
                           f"logit by {gap.max():.4g} > {LOGIT_TOL}")


def phase_router(cfg, params, policy, reqs, n_groups, n_replicas):
    """Affinity Router with one replica per chip against a 1-replica
    Router on the same shared-prefix traffic."""
    from repro.serve.api import EngineConfig
    from repro.serve.frontend import FleetConfig, Router

    def drive(n):
        router = Router(cfg, params, policy, fleet=FleetConfig(
            engine=EngineConfig(**ENGINE, prefix_cache=True,
                                prefix_pages=128),
            n_replicas=n, routing="affinity"))
        try:
            handles, outs = serve(router, reqs, n_leaders=n_groups)
            if not router.drain(timeout=600):
                raise RuntimeError("router did not drain")
            placed = []
            for fe in router.replicas:
                core = fe.engine.core
                (p_dev,) = core.params["embed"].devices()
                (k_dev,) = core.cache.k_pool.devices()
                (m_dev,) = core.cache.mass_pool.devices()
                placed.append((p_dev, k_dev, m_dev))
            st = router.stats()
        finally:
            router.shutdown()
        check_outputs(reqs, handles, outs, cfg.vocab_size)
        return handles, outs, placed, st

    handles, outs, placed, st = drive(n_replicas)
    devs = [p for p, _, _ in placed]
    if any(len(set(t)) != 1 for t in placed) or len(set(devs)) != n_replicas:
        raise RuntimeError(f"replica placement (params, k_pool, mass_pool): "
                           f"{placed}")
    _, outs1, _, st1 = drive(1)
    same = [np.array_equal(a, b) for a, b in zip(outs, outs1)]
    tok_eq = sum(int(np.sum(a == b)) for a, b in zip(outs, outs1))
    record(
        f"router_{n_replicas}_replicas", reqs, handles, outs,
        {k: sum(r["engine"][k] for r in st["replicas"])
         for k in ("compile_s", "steps", "mixed_steps", "spec_steps",
                   "decides", "prefix_hits")},
        replica_devices=[str(d) for d in devs], routed=st["routed"],
        hit_rate=st["aggregate"]["hit_rate"],
        hit_rate_1_replica=st1["aggregate"]["hit_rate"],
        requests_identical_to_1_replica=int(sum(same)),
        token_agreement_with_1_replica=tok_eq / sum(len(o) for o in outs))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the Router phase, one replica per "
                         "chip, against a 1-replica Router")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU visible (JAX platform "
              f"{devices[0].platform!r}); refusing to run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.core.drrl import init_agent
    from repro.models.api import get_model
    cfg = get_config("drrl-paper")
    params = jax.jit(get_model(cfg).init)(jax.random.PRNGKey(SEED))
    policy = init_agent(jax.random.PRNGKey(7), cfg.rank, cfg.d_model)
    rng = np.random.default_rng(SEED)
    V = cfg.vocab_size

    if args.chips == 4:
        reqs = shared_prefix_traffic(rng, V, 4 * N_REQUESTS // 2, n_groups=4)
        phase_router(cfg, params, policy, reqs, n_groups=4, n_replicas=4)
    else:
        reqs = mixed_traffic(rng, V, N_REQUESTS)
        phase_default(cfg, params, policy, reqs)
        gc.collect()
        phase_kernel(cfg, params, policy, reqs)
        gc.collect()
        phase_prefix_spec(cfg, params, policy,
                          shared_prefix_traffic(rng, V, N_REQUESTS, 2), 2)
        gc.collect()
        phase_reference(cfg, params, mixed_traffic(rng, V, N_REQUESTS))

    d = jax.devices()[0]
    emit({"ok": True, "device": {"platform": d.platform,
                                 "kind": d.device_kind,
                                 "count": len(jax.devices())}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
