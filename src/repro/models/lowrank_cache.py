"""Beyond-paper serving optimization: a rank-r KV cache.

The paper truncates the *score contraction* at serve time; the same spectral
machinery (Gram eigenbasis of K over the prompt, repro.core.lowrank) lets us
store the cache itself in factor form:

    k~ = K . E_r   (b, M, hkv, r)   instead of   K (b, M, hkv, d)

cutting decode cache memory AND read bandwidth by r/d — on the decode_32k
cell the KV cache is the dominant memory term after the §Perf split-KV fix,
so this directly attacks the remaining roofline bound. New tokens are
projected onto the prefill basis; the basis can be refreshed every segment with
incremental subspace extension (Eq. 12) — the serving engine re-decides the
bucket anyway, so a refresh is a bucket switch.

V is kept full here (scores drive the quality trade-off; value truncation is
available separately via RankConfig.truncate_values).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro import nn
from repro.configs.base import ModelConfig
from repro.core import lowrank as lr
from repro.models.attention import attend
from repro.models.common import apply_rope, repeat_kv


def init_lowrank_cache(cfg: ModelConfig, batch: int, max_len: int,
                       rank: int) -> Dict:
    dtype = nn.dt(cfg.dtype)
    dh = cfg.resolved_head_dim()
    L, hkv = cfg.num_layers, cfg.num_kv_heads
    return {
        "kt": jnp.zeros((L, batch, max_len, hkv, rank), dtype),
        "v": jnp.zeros((L, batch, max_len, hkv, dh), dtype),
        "basis": jnp.zeros((L, batch, hkv, dh, rank), jnp.float32),
        "len": jnp.zeros((), jnp.int32),
    }


def attention_mass(q: jnp.ndarray, k: jnp.ndarray,
                   q_len=None) -> jnp.ndarray:
    """Per-key attention mass of the prompt's causal self-attention,
    summed over queries and averaged over the q-heads of each kv group.

    q: (L, b, s, hq, d); k: (L, b, s, hkv, d). Returns (L, b, hkv, s)
    normalised so the weights sum to the number of contributing queries
    (scale-free for eigenvectors, but keeps the weighted Gram's trace
    comparable to the plain one, whose weights are 1 per key).

    ``q_len`` (scalar, may be traced) restricts the query average to
    positions < q_len: the serve prefill runs on a padded length bucket,
    and the garbage queries beyond the prompt would otherwise scatter
    score mass back onto real keys.

    Computed one layer at a time (lax.map) so the peak score tensor is
    (b, hq, s, s), matching the forward's own attention peak, instead of
    L times that."""
    L, b, s, hq, dh = q.shape
    hkv = k.shape[3]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    n_q = jnp.asarray(s if q_len is None else q_len, jnp.float32)
    q_ok = (None if q_len is None
            else (jnp.arange(s) < q_len).astype(jnp.float32))

    def one_layer(qk):
        from repro.models.common import kv_group_mean
        q_l, k_l = qk                              # (b, s, hq|hkv, d)
        kr = (jnp.repeat(k_l, hq // hkv, axis=2) if hq != hkv else k_l)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q_l.astype(jnp.float32),
                        kr.astype(jnp.float32)) * dh ** -0.5
        sc = jnp.where(causal[None, None], sc, -1e30)
        p = jax.nn.softmax(sc, axis=-1)
        if q_ok is not None:
            p = p * q_ok[None, None, :, None]
        return kv_group_mean(jnp.sum(p, axis=2), hkv)

    w = jax.lax.map(one_layer, (q, k))             # (L, b, hkv, s)
    return w * n_q / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-30)


def prefill_lowrank(cfg: ModelConfig, params, tokens: jnp.ndarray,
                    cache: Dict, rank: int, *,
                    weighted: bool = True) -> Tuple[jnp.ndarray, Dict]:
    """Run the prompt through the model, build per-(layer, head) bases from
    the prompt K-Grams, and store the truncated cache.

    ``weighted=True`` uses the softmax-weighted Gram G = K^T diag(w) K with
    w the prompt's per-key attention mass: the basis concentrates on the
    directions that actually receive score mass, instead of K's raw energy
    (which can sit where Q never looks — the failure mode recorded in
    ROADMAP for the plain prompt-K basis).

    Returns (last-token logits, filled cache)."""
    from repro.models import transformer as tr
    # capture per-layer K/V via the rl-collection path (any rank mode works;
    # 'adaptive' keeps the forward full-precision while exposing qkv)
    cfg_cap = cfg.with_(rank=cfg.rank.__class__(
        mode="adaptive", rank_grid=cfg.rank.rank_grid or (rank,),
        energy_threshold=1.0))
    logits, aux = tr.forward_dense(cfg_cap, params, tokens,
                                   collect_aux="rl", collect_qkv=True,
                                   rank_rng=jax.random.PRNGKey(0))
    qkv = aux["layers"]["qkv"]                     # k,v: (L, b, s, hkv, d)
    k, v = qkv["k"], qkv["v"]
    L, b, s, hkv, dh = k.shape
    if weighted:
        w = attention_mass(qkv["q"], k)            # (L, b, hkv, s)
        kf = k.astype(jnp.float32)
        gk = jnp.einsum("lbshd,lbhs,lbshe->lbhde", kf, w, kf)
        gk = gk.reshape(L * b * hkv, dh, dh)
    else:
        gk = lr.gram(jnp.moveaxis(k, 3, 2).reshape(L * b * hkv, s, dh))
    _, evecs = lr.gram_spectrum(gk)                # (Lbh, d, d)
    basis = evecs[..., :rank].reshape(L, b, hkv, dh, rank)
    kt = jnp.einsum("lbshd,lbhdr->lbshr", k.astype(jnp.float32), basis)
    kt_full = jax.lax.dynamic_update_slice(
        cache["kt"], kt.astype(cache["kt"].dtype), (0, 0, 0, 0, 0))
    v_full = jax.lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0, 0))
    return logits[:, -1:], {
        "kt": kt_full, "v": v_full, "basis": basis,
        "len": jnp.asarray(s, jnp.int32),
    }


def decode_step_lowrank(cfg: ModelConfig, params, cache: Dict,
                        tokens: jnp.ndarray) -> Tuple[jnp.ndarray, Dict]:
    """One decode step against the rank-r cache: q and the new k are
    projected onto the stored basis; the score contraction runs over r."""
    dtype = nn.dt(cfg.dtype)
    x = params["embed"][tokens].astype(dtype)
    b, s, d = x.shape
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    dh = cfg.resolved_head_dim()
    n_rep = hq // hkv
    positions = jnp.broadcast_to(cache["len"] + jnp.arange(s)[None], (b, s))

    def body(x, xs):
        lp, kt_l, v_l, basis_l = xs
        p = lp["attn"]
        h = nn.rms_norm(x, lp["ln1"], cfg.rms_eps)
        q = jnp.einsum("bsd,dhf->bshf", h, p["wq"].reshape(d, hq, dh).astype(x.dtype))
        k = jnp.einsum("bsd,dhf->bshf", h, p["wk"].reshape(d, hkv, dh).astype(x.dtype))
        v = jnp.einsum("bsd,dhf->bshf", h, p["wv"].reshape(d, hkv, dh).astype(x.dtype))
        if cfg.qkv_bias:
            q = q + p["bq"].reshape(hq, dh).astype(x.dtype)
            k = k + p["bk"].reshape(hkv, dh).astype(x.dtype)
            v = v + p["bv"].reshape(hkv, dh).astype(x.dtype)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        # project onto the prefill basis
        basis_q = jnp.repeat(basis_l, n_rep, axis=1)          # (b, hq, d, r)
        qt = jnp.einsum("bshf,bhfr->bshr", q.astype(jnp.float32), basis_q)
        kt_new = jnp.einsum("bshf,bhfr->bshr", k.astype(jnp.float32), basis_l)
        idx = cache["len"]
        kt_l = jax.lax.dynamic_update_slice(
            kt_l, kt_new.astype(kt_l.dtype), (0, idx, 0, 0))
        v_l = jax.lax.dynamic_update_slice(
            v_l, v.astype(v_l.dtype), (0, idx, 0, 0))
        kv_len = idx + s
        o = attend(qt.astype(x.dtype), repeat_kv(kt_l, n_rep),
                   repeat_kv(v_l, n_rep), scale=dh ** -0.5, causal=True,
                   q_offset=idx, kv_len=kv_len)
        x = x + jnp.einsum("bshf,hfd->bsd", o,
                           p["wo"].reshape(hq, dh, d).astype(x.dtype))
        ffn = lp["ffn"]
        x = x + nn.swiglu(nn.rms_norm(x, lp["ln2"], cfg.rms_eps),
                          ffn["w_gate"], ffn["w_up"], ffn["w_down"])
        return x, (kt_l, v_l)

    from repro.models.common import scan_or_unroll
    x, (kt, v) = scan_or_unroll(
        body, x, (params["layers"], cache["kt"], cache["v"], cache["basis"]),
        unroll=not cfg.scan_layers)
    x = nn.rms_norm(x, params["ln_f"], cfg.rms_eps)
    head = params.get("lm_head")
    logits = (jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
              if head is not None else
              jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(x.dtype)))
    return logits, dict(cache, kt=kt, v=v, len=cache["len"] + s)
