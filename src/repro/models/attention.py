"""Multi-head attention with DR-RL dynamic low-rank score contraction.

Three realisations of the paper's technique live here:
  * full-rank reference (rank.mode == 'off')
  * 'masked' — rank expressed by zeroing eigendirections; single executable,
    differentiable, used for RL training/rollouts and the heuristic baselines
  * 'static' — rank baked into the program (serving buckets; the Pallas
    lowrank_flash kernel consumes the rank-r factors)

Spectral quantities come from the Gram route in repro.core.lowrank; the
perturbation guardrail from repro.core.perturbation.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RankConfig
from repro.core import lowrank as lr
from repro.core import perturbation as pert
from repro.models.common import (apply_mrope, apply_rope, group_q_heads,
                                 repeat_kv, ungroup_q_heads)


# ---------------------------------------------------------------------------
# Score/softmax/value core
# ---------------------------------------------------------------------------

def attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
           scale: float, causal: bool, q_offset: int | jnp.ndarray = 0,
           kv_len: Optional[jnp.ndarray] = None,
           chunked: bool = False, chunk: int = 1024,
           score_dtype=jnp.float32,
           score_spec=None, return_probs: bool = False) -> jnp.ndarray:
    """softmax(q k^T * scale) v.

    ``return_probs`` also returns the probability tensor (b, hq, sq, skv)
    (the serving engine's attention-mass feed); unsupported on the
    chunked path, which never materialises it.

    q: (b, sq, hq, dq)  k: (b, skv, hkv, dq)  v: (b, skv, hkv, dv).
    ``dq`` may be a truncated rank r — the caller supplies the proper scale
    (always 1/sqrt(d_head_original), per the paper's Eq. 1).
    kv_len masks out cache positions >= kv_len. ``chunked`` streams over KV
    blocks with a running softmax (flash semantics in pure XLA).

    GQA: ``hkv`` may divide ``hq``. The score and PV contractions run per
    kv group over q viewed as (b, n_rep * sq, hkv, dq) rows
    (:func:`group_q_heads`), so each kv head is read once instead of being
    repeated ``n_rep`` times first; with ``hkv == hq`` that view is q
    itself. The result, and the probs, equal those of
    ``attend(q, repeat_kv(k), repeat_kv(v))`` with the same head order
    (q head ``g * n_rep + n`` reads kv head ``g``). The chunked path needs
    ``hkv == hq``.

    Perf knobs (EXPERIMENTS.md §Perf): ``score_dtype=bf16`` stores the s^2
    score/prob tensors in bf16 (denominator still accumulated in f32);
    ``score_spec`` applies a sharding constraint to the score tensor
    (sequence-parallel attention: P(dp, None, 'model', None)).
    """
    b, sq, hq, _ = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    if hq % hkv:
        raise ValueError(f"{hkv} kv heads do not divide {hq} q heads")
    n_rep = hq // hkv
    if chunked and skv > chunk:
        if return_probs:
            raise ValueError("return_probs is unsupported on the chunked "
                             "path (probs are never materialised)")
        if n_rep > 1:
            raise ValueError("the chunked path needs as many kv heads as "
                             "q heads: repeat_kv first")
        return _attend_chunked(q, k, v, scale=scale, causal=causal,
                               q_offset=q_offset, kv_len=kv_len, chunk=chunk)
    # (b, hkv, n_rep * sq, skv) -> (b, hq, sq, skv) in repeat_kv's order
    s = jnp.einsum("bmgd,bkgd->bgmk", group_q_heads(q, hkv), k
                   ).reshape(b, hq, sq, skv)
    s = s.astype(score_dtype) * scale
    neg = jnp.asarray(-1e30, score_dtype)
    if causal:
        q_pos = jnp.arange(sq)[:, None] + q_offset
        k_pos = jnp.arange(skv)[None, :]
        s = jnp.where((k_pos <= q_pos)[None, None], s, neg)
    if kv_len is not None:
        valid = jnp.arange(skv)[None, None, None, :] < kv_len
        s = jnp.where(valid, s, neg)
    if score_spec is not None:
        s = jax.lax.with_sharding_constraint(s, score_spec)
    if score_dtype == jnp.float32:
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    else:
        # bf16 score chain: elementwise ops stay bf16 (halving the dominant
        # s^2 HBM traffic); the sum is accumulated in f32 (small tensor)
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - m)
        denom = jnp.sum(e.astype(jnp.float32), axis=-1, keepdims=True)
        p = (e / jnp.maximum(denom, 1e-30).astype(score_dtype)).astype(v.dtype)
    out = ungroup_q_heads(jnp.einsum(
        "bgmk,bkgd->bmgd", p.reshape(b, hkv, n_rep * sq, skv), v), sq)
    return (out, p) if return_probs else out


def _attend_chunked(q, k, v, *, scale, causal, q_offset, kv_len, chunk):
    """Streaming-softmax attention over KV chunks (never materialises the
    full (sq, skv) score matrix in HBM — XLA analogue of flash attention)."""
    b, sq, h, dq = q.shape
    skv, dv = k.shape[1], v.shape[-1]
    n_chunks = (skv + chunk - 1) // chunk
    pad = n_chunks * chunk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(b, n_chunks, chunk, h, dq)
    vc = v.reshape(b, n_chunks, chunk, h, dv)

    q_pos = jnp.arange(sq)[:, None] + q_offset

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, ci = xs
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kb).astype(jnp.float32) * scale
        k_pos = ci * chunk + jnp.arange(chunk)[None, :]
        mask = k_pos < (kv_len if kv_len is not None else skv)
        if causal:
            mask = mask & (k_pos <= q_pos)
        s = jnp.where(mask[None, None] if mask.ndim == 2 else mask, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(vb.dtype), vb).astype(jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, h, sq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(v.dtype)


# ---------------------------------------------------------------------------
# Rank decision + projection
# ---------------------------------------------------------------------------

def spectral_ctx(q: jnp.ndarray, k: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Per-head Gram spectra of q (b,s,hq,d) and k (b,s,hkv,d).

    Shapes: sigmas (b, h, d) descending; evecs (b, h, d, d)."""
    gq = lr.gram(jnp.swapaxes(q, 1, 2))            # (b, hq, d, d)
    gk = lr.gram(jnp.swapaxes(k, 1, 2))
    q_s2, q_e = lr.gram_spectrum(gq)
    k_s2, k_e = lr.gram_spectrum(gk)
    return {"q_s2": q_s2, "q_e": q_e, "k_s2": k_s2, "k_e": k_e}


def grid_array(rank_cfg: RankConfig) -> jnp.ndarray:
    return jnp.asarray(rank_cfg.rank_grid, jnp.int32)


def heuristic_rank(rank_cfg: RankConfig, ctx: Dict[str, jnp.ndarray],
                   rng: Optional[jax.Array]) -> jnp.ndarray:
    """Rank per (b, hkv) for the non-RL modes (fixed/adaptive/random)."""
    k_s2 = ctx["k_s2"]
    b, h = k_s2.shape[0], k_s2.shape[1]
    grid = rank_cfg.rank_grid
    if rank_cfg.mode == "fixed":
        return jnp.full((b, h), rank_cfg.fixed_rank, jnp.int32)
    if rank_cfg.mode == "adaptive":
        return lr.rank_for_energy(k_s2, rank_cfg.energy_threshold,
                                  grid[0], grid[-1])
    if rank_cfg.mode == "random":
        assert rng is not None, "random mode needs a PRNG key"
        idx = jax.random.randint(rng, (b, h), 0, len(grid))
        return jnp.asarray(grid, jnp.int32)[idx]
    raise ValueError(rank_cfg.mode)


def apply_rank_masked(q, k, ctx, rank_q: jnp.ndarray, rank_k: jnp.ndarray):
    """Project q/k onto their top-rank eigendirections ('masked' realisation).

    rank_q: (b, hq); rank_k: (b, hkv) traced ints."""
    d = q.shape[-1]
    mq = (jnp.arange(d)[None, None, :] < rank_q[..., None]).astype(jnp.float32)
    mk = (jnp.arange(d)[None, None, :] < rank_k[..., None]).astype(jnp.float32)
    qh = jnp.swapaxes(q, 1, 2)                      # (b, h, s, d)
    kh = jnp.swapaxes(k, 1, 2)
    q_r = lr.project_masked(qh, ctx["q_e"], mq)
    k_r = lr.project_masked(kh, ctx["k_e"], mk)
    return jnp.swapaxes(q_r, 1, 2), jnp.swapaxes(k_r, 1, 2)


def apply_rank_static(q, k, ctx, r: int):
    """Rank-r factors for the serving bucket: returns q~ (b,s,hq,r),
    k~ (b,s,hkv,r) such that q~ k~^T == Q_r K_r^T (both sides truncated)."""
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    n_rep = q.shape[2] // k.shape[2]
    eq, ek = ctx["q_e"], ctx["k_e"]
    ek_rep = jnp.repeat(ek, n_rep, axis=1) if n_rep > 1 else ek
    m = lr.mixing_matrix(eq, ek_rep, r)             # (b, hq, r, r)
    q_t = lr.project_static(qh, eq, r)              # (b, hq, s, r)
    q_t = jnp.einsum("bhsr,bhrt->bhst", q_t.astype(jnp.float32), m).astype(q.dtype)
    k_t = lr.project_static(kh, ek, r)              # (b, hkv, s, r)
    return jnp.swapaxes(q_t, 1, 2), jnp.swapaxes(k_t, 1, 2)


# ---------------------------------------------------------------------------
# Full MHSA layer (projection + rope + rank logic + attend + output proj)
# ---------------------------------------------------------------------------

def mhsa(cfg: ModelConfig, p: Dict[str, Any], x: jnp.ndarray,
         positions: jnp.ndarray, *,
         rank_ctx: Optional[Dict[str, Any]] = None,
         cache: Optional[dict] = None,
         chunked: bool = False) -> Tuple[jnp.ndarray, Optional[dict], Dict[str, Any]]:
    """Standard/GQA MHSA with optional dynamic low-rank scores.

    rank_ctx (None = full rank): {
       'cfg': RankConfig, 'rng': key|None,
       'action_fn': callable(features)->(rank_q, rank_k, aux) for drrl mode,
       'prev_rank': (b, hkv) carry, 't': rl step for the annealed guardrail }
    Returns (output, new_cache, aux).
    """
    b, s, d = x.shape
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    dh = cfg.resolved_head_dim()
    q = jnp.einsum("bsd,dhf->bshf", x, p["wq"].reshape(d, hq, dh).astype(x.dtype))
    k = jnp.einsum("bsd,dhf->bshf", x, p["wk"].reshape(d, hkv, dh).astype(x.dtype))
    v = jnp.einsum("bsd,dhf->bshf", x, p["wv"].reshape(d, hkv, dh).astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].reshape(hq, dh).astype(x.dtype)
        k = k + p["bk"].reshape(hkv, dh).astype(x.dtype)
        v = v + p["bv"].reshape(hkv, dh).astype(x.dtype)

    if cfg.mrope:
        q = apply_mrope(q, positions, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, positions, cfg.mrope_sections, cfg.rope_theta)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    q_offset, kv_len, new_cache = 0, None, None
    if cache is not None:
        from repro.models.common import cache_update
        new_cache = cache_update(cache, k, v)
        k_full, v_full = new_cache["k"], new_cache["v"]
        q_offset, kv_len = cache["len"], new_cache["len"]
        if cfg.cache_seq_shard and cfg.mesh_axes:
            # split-KV decode: keep the cache in its stored layout — context
            # dim M sharded over 'model' — all the way through attention;
            # the partial-softmax combine is the only cross-shard traffic
            from jax.sharding import PartitionSpec as P
            dp = tuple(a for a in cfg.mesh_axes if a != "model")
            dp = dp if len(dp) > 1 else (dp[0] if dp else None)
            k_full = jax.lax.with_sharding_constraint(
                k_full, P(dp, "model", None, None))
            v_full = jax.lax.with_sharding_constraint(
                v_full, P(dp, "model", None, None))
    else:
        k_full, v_full = k, v

    aux: Dict[str, Any] = {}
    scale = dh ** -0.5
    rcfg = rank_ctx["cfg"] if rank_ctx else None
    if rank_ctx is not None and rank_ctx.get("collect_qkv", False):
        # qkv capture works in every rank mode, including 'off' (the serve
        # prefill captures per-layer q/k/v to seed the attention-mass pool
        # without perturbing the full-rank forward)
        aux["qkv"] = {"q": q, "k": k_full, "v": v_full}

    score_dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[
        cfg.softmax_dtype]
    score_spec = None
    if cfg.cache_seq_shard and cfg.mesh_axes and cache is not None:
        from jax.sharding import PartitionSpec as P
        dp = tuple(a for a in cfg.mesh_axes if a != "model")
        dp = dp if len(dp) > 1 else (dp[0] if dp else None)
        score_spec = P(dp, None, None, "model")
    if cfg.seq_shard_attn and cfg.mesh_axes and cache is None:
        # sequence-parallel attention: scores (b, h, sq, skv) sharded over
        # (data..., model) on (batch, query-seq). Robust for every arch:
        # sq % 16 == 0 even when num_heads % 16 != 0 (the case that forced
        # GSPMD to gather the batch — see EXPERIMENTS.md §Perf).
        from jax.sharding import PartitionSpec as P
        dp = tuple(a for a in cfg.mesh_axes if a != "model")
        dp = dp if len(dp) > 1 else (dp[0] if dp else None)
        q = jax.lax.with_sharding_constraint(q, P(dp, "model", None, None))
        score_spec = P(dp, None, "model", None)

    if rcfg is not None and rcfg.mode in ("performer", "nystrom"):
        # static linear-attention baselines (paper Table 1/3 comparison set)
        from repro.core.baselines import nystrom_attention, performer_attention
        n_rep = hq // hkv
        kr, vr = repeat_kv(k_full, n_rep), repeat_kv(v_full, n_rep)
        if rcfg.mode == "performer":
            o = performer_attention(q, kr, vr, proj=rank_ctx["proj"],
                                    causal=cache is None)
        else:
            o = nystrom_attention(q, kr, vr,
                                  n_landmarks=rcfg.fixed_rank,
                                  causal=cache is None)
        out = jnp.einsum("bshf,hfd->bsd", o,
                         p["wo"].reshape(hq, dh, d).astype(x.dtype))
        return out, new_cache, aux

    if rcfg is None or rcfg.mode == "off":
        q_use, k_use = q, k_full
    else:
        ctx = spectral_ctx(q, k_full)
        aux["k_s2"] = ctx["k_s2"]
        if rcfg.mode == "drrl":
            rank_k, drrl_aux = rank_ctx["action_fn"](ctx, rank_ctx)
            aux.update(drrl_aux)
        else:
            rank_k = heuristic_rank(rcfg, ctx, rank_ctx.get("rng"))
        n_rep = hq // hkv
        rank_q = jnp.repeat(rank_k, n_rep, axis=1) if n_rep > 1 else rank_k
        aux["rank"] = rank_k
        q_s2_kv = (ctx["q_s2"].reshape(b, hkv, hq // hkv, dh).mean(2)
                   if hq != hkv else ctx["q_s2"])
        bounds, norm = pert.guardrail_report(q_s2_kv, ctx["k_s2"],
                                             rcfg.rank_grid, dh)
        aux["delta_a_grid"] = bounds
        aux["delta_a_norm"] = norm
        if rcfg.realisation == "static":
            r = rcfg.static_rank or int(rcfg.rank_grid[-1])
            q_use, k_use = apply_rank_static(q, k_full, ctx, r)
        else:
            q_use, k_use = apply_rank_masked(q, k_full, ctx, rank_q, rank_k)
        if rcfg.truncate_values and rcfg.realisation == "masked":
            # value-side truncation (paper Eq. 5/10 analysis): V projected
            # onto its own top-rank eigenbasis; cuts the n^2 d_v term too
            gv = lr.gram(jnp.swapaxes(v_full, 1, 2))
            v_s2, v_e = lr.gram_spectrum(gv)
            mv = (jnp.arange(v_full.shape[-1])[None, None, :]
                  < rank_k[..., None]).astype(jnp.float32)
            v_full = jnp.swapaxes(
                lr.project_masked(jnp.swapaxes(v_full, 1, 2), v_e, mv), 1, 2)
        if rank_ctx.get("compute_fidelity", False):
            # cosine similarity between full-rank and low-rank outputs (Eq. 8)
            o_full = attend(q, repeat_kv(k_full, hq // hkv),
                            repeat_kv(v_full, hq // hkv), scale=scale,
                            causal=True, q_offset=q_offset,
                            kv_len=kv_len, chunked=chunked)
            aux["_o_full"] = o_full

    n_rep = hq // hkv
    k_use_r = repeat_kv(k_use, n_rep)
    v_use = repeat_kv(v_full, n_rep)
    o = attend(q_use, k_use_r, v_use, scale=scale, causal=True,
               q_offset=q_offset, kv_len=kv_len, chunked=chunked,
               score_dtype=score_dtype, score_spec=score_spec)
    if "_o_full" in aux:
        of, ol = aux.pop("_o_full"), o
        num = jnp.sum(of.astype(jnp.float32) * ol.astype(jnp.float32), axis=(1, 3))
        den = (jnp.linalg.norm(of.astype(jnp.float32), axis=(1, 3))
               * jnp.linalg.norm(ol.astype(jnp.float32), axis=(1, 3)) + 1e-30)
        aux["fidelity"] = num / den                # (b, hq) cosine sim
    out = jnp.einsum("bshf,hfd->bsd", o, p["wo"].reshape(hq, dh, d).astype(x.dtype))
    return out, new_cache, aux


def attention_flops(seq: int, kv: int, hq: int, dh: int, dv: int, rank=None) -> float:
    """MAC-counted (x2) attention score+value FLOPs per sequence per head set.
    With rank-r scores the QK^T contraction runs over r instead of dh."""
    c = rank if rank is not None else dh
    return 2.0 * hq * (seq * kv * c + seq * kv * dv)
