"""Dense decoder-only transformer family (llama/qwen/internlm/phi3 style),
plus the Qwen2-VL backbone (M-RoPE) — scan-over-layers with stacked params.

Also hosts the generic FFN/MoE block dispatch used by the MoE family.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import nn
from repro.configs.base import ModelConfig
from repro.core import drrl
from repro.models import moe as moe_mod
from repro.models.attention import mhsa


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_attn(cfg: ModelConfig, rng, dtype) -> Dict[str, jnp.ndarray]:
    d, dh = cfg.d_model, cfg.resolved_head_dim()
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    ks = nn.split_keys(rng, 4)
    p = {
        "wq": nn.dense_init(ks[0], d, hq * dh, dtype),
        "wk": nn.dense_init(ks[1], d, hkv * dh, dtype),
        "wv": nn.dense_init(ks[2], d, hkv * dh, dtype),
        "wo": nn.dense_init(ks[3], hq * dh, d, dtype,
                            scale=(hq * dh) ** -0.5 / (2 * cfg.num_layers) ** 0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * dh,), dtype)
        p["bk"] = jnp.zeros((hkv * dh,), dtype)
        p["bv"] = jnp.zeros((hkv * dh,), dtype)
    return p


def init_ffn(cfg: ModelConfig, rng, dtype, d_ff: Optional[int] = None):
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = nn.split_keys(rng, 3)
    return {
        "w_gate": nn.dense_init(ks[0], d, f, dtype),
        "w_up": nn.dense_init(ks[1], d, f, dtype),
        "w_down": nn.dense_init(ks[2], f, d, dtype,
                                scale=f ** -0.5 / (2 * cfg.num_layers) ** 0.5),
    }


def init_layer(cfg: ModelConfig, rng, dtype) -> Dict[str, Any]:
    k1, k2 = jax.random.split(rng)
    p = {
        "attn": init_attn(cfg, k1, dtype),
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
    }
    if cfg.family == "moe" and cfg.moe is not None:
        p["moe"] = moe_mod.init_moe(cfg, k2, dtype)
    else:
        p["ffn"] = init_ffn(cfg, k2, dtype)
    return p


def init_dense(cfg: ModelConfig, rng) -> Dict[str, Any]:
    dtype = nn.dt(cfg.param_dtype)
    k_emb, k_layers, k_head = jax.random.split(rng, 3)
    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    # always stacked: scan consumes them directly; the unrolled path
    # (scan_layers=False, used by the roofline calibration) slices per layer
    layers = jax.vmap(lambda k: init_layer(cfg, k, dtype))(layer_keys)
    params = {
        "embed": nn.embed_init(k_emb, cfg.vocab_size, cfg.d_model, dtype),
        "layers": layers,
        "ln_f": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = nn.dense_init(k_head, cfg.d_model, cfg.vocab_size, dtype)
    return params


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _block(cfg: ModelConfig, lp, x, positions, rank_ctx, cache, chunked):
    h, new_cache, aux = mhsa(cfg, lp["attn"], nn.rms_norm(x, lp["ln1"], cfg.rms_eps),
                             positions, rank_ctx=rank_ctx, cache=cache,
                             chunked=chunked)
    x = x + h
    if cfg.family == "moe" and cfg.moe is not None and "moe" in lp:
        f, moe_aux = moe_mod.moe_ffn(cfg, lp["moe"], nn.rms_norm(x, lp["ln2"], cfg.rms_eps))
        aux = {**aux, **moe_aux}
    else:
        f = nn.swiglu(nn.rms_norm(x, lp["ln2"], cfg.rms_eps),
                      lp["ffn"]["w_gate"], lp["ffn"]["w_up"], lp["ffn"]["w_down"])
    return x + f, new_cache, aux


def _aux_slim(aux: Dict[str, Any], collect: str) -> Dict[str, Any]:
    """Select which per-layer aux to stack through scan.
    collect: 'none' | 'ranks' | 'rl' (everything PPO needs)."""
    if collect == "none":
        return {}
    keep = {"rank", "delta_a_rel", "fidelity", "aux_loss"}
    if collect == "rl":
        keep |= {"action_idx", "logp", "value", "action_mask", "features",
                 "logits", "delta_a_grid", "delta_a_norm", "k_s2", "qkv"}
    return {k: v for k, v in aux.items() if k in keep}


def make_rank_ctx(cfg: ModelConfig, *, policy_params=None, rng=None, t=0,
                  greedy=True, compute_fidelity=False, h_t=None,
                  collect_qkv=False):
    """Build the per-forward rank context (None when mode == 'off', unless
    qkv capture is requested — the RL rollout and the low-rank cache
    collect per-layer q/k/v from the untouched full-rank forward)."""
    rcfg = cfg.rank
    if rcfg.mode == "off":
        if collect_qkv:
            return {"cfg": rcfg, "rng": rng, "t": t,
                    "compute_fidelity": False, "collect_qkv": True}
        return None
    ctx: Dict[str, Any] = {"cfg": rcfg, "rng": rng, "t": t,
                           "compute_fidelity": compute_fidelity,
                           "collect_qkv": collect_qkv}
    if rcfg.mode == "performer":
        from repro.core.baselines import orthogonal_proj
        dh = cfg.resolved_head_dim()
        m = max(2 * dh, 4 * rcfg.fixed_rank)
        ctx["proj"] = orthogonal_proj(jax.random.PRNGKey(42), cfg.num_heads,
                                      m, dh)
    if rcfg.mode == "drrl":
        assert policy_params is not None, "drrl mode needs policy params"
        if h_t is None:
            raise ValueError("drrl mode: pass h_t (conv features) via forward")
        ctx["action_fn"] = drrl.make_action_fn(policy_params, rcfg,
                                               h_t=h_t, greedy=greedy)
    return ctx


def forward_dense(cfg: ModelConfig, params, tokens, *, positions=None,
                  policy_params=None, rank_rng=None, rl_t=0, greedy=True,
                  compute_fidelity=False, collect_aux: str = "none",
                  chunked: bool = False, collect_qkv: bool = False,
                  return_hidden: bool = False,
                  extra_embeddings: Optional[jnp.ndarray] = None
                  ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """tokens: (b, s) int32 (or (b, s_txt) with extra_embeddings prepended for
    the VLM/audio stub). Returns (logits (b, s, V), aux)."""
    dtype = nn.dt(cfg.dtype)
    x = params["embed"][tokens].astype(dtype)
    if extra_embeddings is not None:
        x = jnp.concatenate([extra_embeddings.astype(dtype), x], axis=1)
    b, s, _ = x.shape
    if positions is None:
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        positions = jnp.broadcast_to(pos[:, None], (b, 3, s)) if cfg.mrope else pos

    rcfg = cfg.rank
    h_t = None
    if rcfg.mode == "drrl":
        h_t = drrl.conv_features(x, policy_params["conv"])
    rank_ctx0 = make_rank_ctx(cfg, policy_params=policy_params, rng=rank_rng,
                              t=rl_t, greedy=greedy,
                              compute_fidelity=compute_fidelity, h_t=h_t,
                              collect_qkv=collect_qkv)

    def body(carry, xs):
        x, prev_rank, key = carry
        lp, li = xs
        rank_ctx = None
        if rank_ctx0 is not None:
            sub = None
            if key is not None:
                key, sub = jax.random.split(key)
            rank_ctx = dict(rank_ctx0, prev_rank=prev_rank, layer_id=li,
                            rng=sub,
                            w_t=(drrl.weight_stats(lp["attn"], rcfg.power_iters)
                                 if rcfg.mode == "drrl" else None))
        x, _, aux = _block(cfg, lp, x, positions, rank_ctx, None, chunked)
        new_prev = aux.get("rank", prev_rank)
        return (x, new_prev, key), _aux_slim(aux, collect_aux)

    prev0 = jnp.full((b, cfg.num_kv_heads), rcfg.rank_grid[-1], jnp.int32)
    key0 = rank_rng
    body_fn = body
    if cfg.remat != "none":
        body_fn = jax.checkpoint(
            body, policy=(jax.checkpoint_policies.checkpoint_dots
                          if cfg.remat == "dots" else None))
    from repro.models.common import scan_or_unroll
    (x, _, _), aux_layers = scan_or_unroll(
        body_fn, (x, prev0, key0),
        (params["layers"], jnp.arange(cfg.num_layers)),
        unroll=not cfg.scan_layers)
    if aux_layers is None:
        aux_layers = {}

    x = nn.rms_norm(x, params["ln_f"], cfg.rms_eps)
    head = params.get("lm_head", None)
    logits = (jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
              if head is not None else
              jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(x.dtype)))
    out_aux: Dict[str, Any] = {"layers": aux_layers}
    if return_hidden:
        out_aux["hidden"] = x
    return logits, out_aux


def loss_dense(cfg: ModelConfig, params, batch, **kw):
    from repro.dist.ctx import logits_spec
    logits, aux = forward_dense(cfg, params, batch["tokens"], **kw)
    n_txt = batch["labels"].shape[1]
    logits = logits[:, -n_txt:]
    loss = nn.softmax_cross_entropy(logits, batch["labels"],
                                    batch.get("mask"),
                                    spec=logits_spec(cfg))
    if aux["layers"] and "aux_loss" in aux["layers"]:
        loss = loss + jnp.mean(aux["layers"]["aux_loss"])
    return loss, aux


# ---------------------------------------------------------------------------
# Decode path (KV caches stacked over layers)
# ---------------------------------------------------------------------------

def init_cache_dense(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    dtype = nn.dt(cfg.dtype)
    dh = cfg.resolved_head_dim()
    L = cfg.num_layers
    return {
        "k": jnp.zeros((L, batch, max_len, cfg.num_kv_heads, dh), dtype),
        "v": jnp.zeros((L, batch, max_len, cfg.num_kv_heads, dh), dtype),
        "len": jnp.zeros((), jnp.int32),
    }


def decode_step_dense(cfg: ModelConfig, params, cache, tokens, *,
                      positions=None, policy_params=None, rank_rng=None,
                      rl_t=0, chunked: bool = False):
    """One decode step: tokens (b, s_new) appended at cache['len'].
    Returns (logits (b, s_new, V), new_cache)."""
    dtype = nn.dt(cfg.dtype)
    x = params["embed"][tokens].astype(dtype)
    b, s, _ = x.shape
    if positions is None:
        pos = cache["len"] + jnp.arange(s)[None]
        pos = jnp.broadcast_to(pos, (b, s))
        positions = jnp.broadcast_to(pos[:, None], (b, 3, s)) if cfg.mrope else pos

    rcfg = cfg.rank
    h_t = None
    if rcfg.mode == "drrl" and policy_params is not None:
        h_t = drrl.conv_features(x, policy_params["conv"])
    rank_ctx0 = make_rank_ctx(cfg, policy_params=policy_params, rng=rank_rng,
                              t=rl_t, greedy=True, h_t=h_t)

    def body(carry, xs):
        x, prev_rank = carry
        lp, li, ck, cv = xs
        layer_cache = {"k": ck, "v": cv, "len": cache["len"]}
        rank_ctx = None
        if rank_ctx0 is not None:
            rank_ctx = dict(rank_ctx0, prev_rank=prev_rank, layer_id=li,
                            w_t=(drrl.weight_stats(lp["attn"], rcfg.power_iters)
                                 if rcfg.mode == "drrl" else None))
        x, new_cache, aux = _block(cfg, lp, x, positions, rank_ctx,
                                   layer_cache, chunked)
        return (x, aux.get("rank", prev_rank)), (new_cache["k"], new_cache["v"])

    prev0 = jnp.full((b, cfg.num_kv_heads), rcfg.rank_grid[-1], jnp.int32)
    from repro.models.common import scan_or_unroll
    (x, _), (nk, nv) = scan_or_unroll(
        body, (x, prev0),
        (params["layers"], jnp.arange(cfg.num_layers), cache["k"], cache["v"]),
        unroll=not cfg.scan_layers)
    x = nn.rms_norm(x, params["ln_f"], cfg.rms_eps)
    head = params.get("lm_head", None)
    logits = (jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
              if head is not None else
              jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(x.dtype)))
    return logits, {"k": nk, "v": nv, "len": cache["len"] + s}


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def paged_attention_selected(cfg: ModelConfig, *, chunk: int, max_len: int,
                             page_size: int, pool_dtype, factored: bool,
                             mass_defer: bool = False) -> bool:
    """Whether the fused step attends with the Pallas kernel that reads
    the K/V pages in place (:mod:`repro.kernels.paged_attn`): on a TPU,
    where K is stored dense (no factor pool) and the step accumulates its
    attention mass in-graph (the speculative verify defers it per query
    instead), for shapes the kernel serves. Everywhere else, the CPU among
    them, the step gathers the pages and attends in XLA."""
    from repro.kernels.paged_attn import fits
    return (_on_tpu() and not factored and not mass_defer
            and fits(chunk=chunk, hq=cfg.num_heads, hkv=cfg.num_kv_heads,
                     dh=cfg.resolved_head_dim(), max_len=max_len,
                     page_size=page_size,
                     pool_itemsize=jnp.dtype(pool_dtype).itemsize))


def decode_step_paged(cfg: ModelConfig, params, pool_k, pool_v, page_table,
                      tokens, *, slot_lens, slot_ranks=None, basis=None,
                      active=None, use_kernel: bool = False,
                      kt_pool=None, mass_pool=None,
                      q_lens=None, prefill_rows=None,
                      return_all_logits: bool = False,
                      mass_defer: bool = False):
    """One fused decode step over every serving slot of a slot-paged cache
    (repro.serve): heterogeneous streams share ONE executable.

    pool_k/pool_v: (L, P, page_size, hkv * dh) shared page pools, pages
    major and each position's heads flattened into one row (see
    repro.serve.kv_cache);
    page_table: (n_slots, pages_per_slot) physical page ids (page 0 is the
    scratch page); tokens: (n_slots, C) int32 (C = 1 for pure decode);
    slot_lens: (n_slots,) valid prefix length per slot BEFORE this step;
    slot_ranks: (n_slots,) rank bucket per slot with basis
    (L, n_slots, hkv, dh, r_max) the per-slot segment eigenbases (both
    None only for rank mode 'off'); active: (n_slots,) bool — inactive
    rows write to the scratch page and their logits are garbage the
    engine ignores.

    **Chunked prefill** (repro.serve.api): with C > 1 each row carries a
    block of query tokens. ``q_lens`` (n_slots,) gives the number of valid
    queries per row (1 for decode rows, up to C for a mid-prefill row's
    prompt chunk) and ``prefill_rows`` (n_slots,) bool marks rows that are
    mid-prefill: those attend **full-rank dense** (their segment basis
    does not exist yet; parity with the plain forward requires the
    untouched scores), causally within the chunk, while decode rows in the same
    executable keep the factor-projected rank path — the two score reads
    are built at head-dim width (factor columns zero-padded, adding exact
    0.0 terms) and selected per row. Returned logits are the **last valid
    query's** per row: the next decode token for decode rows, the first
    generated token for a row finishing its prompt, garbage mid-prompt.

    Per-row dynamic shape is expressed statically: per-(row, query) kv_len
    feeds the attention mask (or the per-row flash-decode kernel when
    ``use_kernel``), and per-row rank is factor padding + rank masking —
    the projected q factors are padded to r_max columns with columns beyond
    the slot's rank zeroed, so the widened score contraction only adds
    exact zeros. No spectral solve happens here: the basis is refreshed by
    the segment decision (Eq. 12).

    ``kt_pool`` (L, n_slots + 1, M, hkv, r_max), when given, is the K
    cache in factor form kt = K . B_r under each slot's segment basis:
    the score contraction then reads the factors (r_max/d of the dense K
    bytes) instead of gathering + projecting dense K. Unlike K/V the
    factors are **slot-indexed**, not paged: they depend on the slot's
    own basis, so two slots sharing a physical prefix page (serve/prefix)
    hold different factors of the same keys. Row n_slots is a scratch row
    absorbing dead-lane / padding-column writes. New tokens' factors are
    appended in-graph; dense K is still written (basis refresh / drift
    need it) but not read there. A mid-prefill row's appended factors are
    placeholders — its first segment decision re-projects the whole slot
    before any factor read.

    ``mass_pool`` (L, n_slots, M, hkv), when given, accumulates each
    key's received softmax mass in-graph (group-mean over the q heads of
    each kv head): the weighted-Gram input of the next segment decision.
    Also slot-indexed: mass is per-*stream* state (which queries
    attended), so a shared prefix page receives different mass from each
    sharing slot. A prefill chunk's queries add their causal mass over
    the full prefix — chunk-by-chunk accumulation sums to the whole
    prompt's causal mass, so the weighted basis still sees all of it. A
    cell is reset in-graph the step its position is appended (before the
    add), so recycled slots never leak a previous occupant's
    mass; a prefix-hit slot's matched region is instead re-seeded from
    the tree snapshot at admission and only ever added to here.

    **Speculative verify** (repro.serve.spec): ``return_all_logits`` keeps
    every query's logits — (n_slots, C, V) instead of the last valid
    query's — so one chunked step can score a row's whole draft run.
    ``mass_defer`` replaces the in-graph mass accumulate with per-query
    contributions returned under pools["mass_q"] (L, n_slots, C, M, hkv):
    the caller applies only the accepted prefix's queries after the
    accept length is known, so rejected drafts never pollute the
    weighted-Gram state feeding the next segment decision (Eq. 9 veto
    must see accepted tokens only). Causality makes the deferred sum of
    accepted queries bitwise equal to the sequential one-token updates.

    **The pools stay in place.** The layer scan carries the stacked
    ``k``/``v`` (and ``kt``/``mass``) pools instead of slicing them as its
    xs and restacking them as its ys: each layer scatters its new tokens
    at ``[layer, page, offset]`` and gathers its pages with one
    ``pool[layer, page_table]``, both on the whole stack, so a step whose
    caller donates the pools touches only the positions it writes and the
    pages it reads, whatever the pool's size.

    **Paged read** (:func:`paged_attention_selected`): on a TPU with dense
    K and the mass accumulated in-graph, no page is gathered at all. The
    Pallas kernel :func:`repro.kernels.paged_attn.paged_decode_attention`
    reads each row's live pages from the carried pools through the page
    table and stops at the row's ``kv_end``. K is read as stored, so the
    rank projection moves to the query side, an exact re-association for
    any basis: ``(q B ⊙ col_ok)(K B)ᵀ = ((q B ⊙ col_ok) Bᵀ) Kᵀ``. Decode
    rows take the re-projected q, mid-prefill rows keep q.

    Returns (logits (n_slots, 1, V), pools) with pools a dict holding the
    updated ``k``/``v`` pools plus ``kt``/``mass`` when those were given.
    """
    from repro.models.attention import attend
    from repro.models.common import apply_rope, group_q_heads, ungroup_q_heads
    if cfg.mrope:
        raise ValueError("paged decode does not support M-RoPE streams")
    if (slot_ranks is None) != (basis is None):
        raise ValueError("slot_ranks and basis must be given together")
    if (kt_pool is not None or mass_pool is not None) and slot_ranks is None:
        raise ValueError("kt_pool/mass_pool require the rank path")
    if mass_defer and slot_ranks is None:
        raise ValueError("mass_defer requires the rank path")
    if mass_defer and mass_pool is not None:
        raise ValueError("mass_defer and mass_pool are mutually exclusive: "
                         "deferred contributions are applied by the caller")
    dtype = nn.dt(cfg.dtype)
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(dtype)
    ns, C = tokens.shape
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    dh = cfg.resolved_head_dim()
    d = cfg.d_model
    ps = pool_k.shape[2]
    n_pp = page_table.shape[1]
    M = n_pp * ps
    rcfg = cfg.rank
    # ``mixed`` is trace-time static: the pure-decode executable keeps the
    # lean factor-only read path; the mixed executable builds both score
    # reads and selects per row
    mixed = prefill_rows is not None
    if active is None:
        active = jnp.ones((ns,), bool)
    if q_lens is None:
        q_lens = jnp.ones((ns,), jnp.int32)
    is_pf = (jnp.zeros((ns,), bool) if prefill_rows is None
             else prefill_rows & active)
    j_idx = jnp.arange(C)[None, :]                            # (1, C)
    positions = slot_lens[:, None] + j_idx                    # (ns, C)
    # physical write coordinates for the new tokens (scratch for dead
    # lanes and for padding columns beyond a row's q_len)
    write_ok = (j_idx < q_lens[:, None]) & active[:, None]
    pg = jnp.minimum(positions // ps, n_pp - 1)
    phys = jnp.where(write_ok, jnp.take_along_axis(page_table, pg, axis=1), 0)
    off = jnp.where(write_ok, positions % ps, 0)
    kv_end = slot_lens + q_lens                               # keys after write
    # per-(row, query) visible length; padding queries clamp to the last
    # valid query's window so no softmax row is ever fully masked
    kv_len_q = (slot_lens[:, None]
                + jnp.minimum(j_idx, q_lens[:, None] - 1) + 1)  # (ns, C)
    valid = jnp.arange(M)[None, :] < kv_end[:, None]            # (ns, M)
    # slot-indexed write coordinates for the per-slot kt rows: padding
    # columns / dead lanes land on scratch row ns instead of a phys page
    slot_rows = jnp.where(write_ok, jnp.arange(ns)[:, None], ns)
    slot_pos = jnp.where(write_ok, jnp.minimum(positions, M - 1), 0)
    # a position's mass cell is reset exactly once — in the step that
    # appends it — so recycled slots never leak a previous occupant's
    # mass and admission needs no eager pool-wide zeroing
    new_cell = (valid & (jnp.arange(M)[None, :] >= slot_lens[:, None])
                & active[:, None])
    score_dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[
        cfg.softmax_dtype]
    scale = dh ** -0.5
    paged = paged_attention_selected(
        cfg, chunk=C, max_len=M, page_size=ps, pool_dtype=pool_k.dtype,
        factored=kt_pool is not None, mass_defer=mass_defer)
    if slot_ranks is not None:
        r_keep = basis.shape[-1]
        col_ok = (jnp.arange(r_keep)[None, :]
                  < jnp.minimum(slot_ranks, r_keep)[:, None]
                  ).astype(jnp.float32)             # (ns, r_keep)

    def body(carry, xs):
        # ``li`` is a traced scalar under lax.scan and a static numpy int
        # in the unrolled form; either way every pool access below
        # indexes the carried stack, never a per-layer copy
        x, kp, vp, extra = carry
        lp, li, basis_l = xs
        ktp, mp = extra.get("kt"), extra.get("mass")
        p = lp["attn"]
        with jax.named_scope("qkv"):
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
        with jax.named_scope("kv_write"):
            kp = kp.at[li, phys, off].set(
                k.reshape(ns, C, hkv * dh).astype(kp.dtype))
            vp = vp.at[li, phys, off].set(
                v.reshape(ns, C, hkv * dh).astype(vp.dtype))
            if ktp is not None:
                # factor-form cache: append the new tokens' factors under
                # the slot's segment basis
                kt_new = jnp.einsum("bshd,bhdr->bshr",
                                    k.astype(jnp.float32), basis_l)
                ktp = ktp.at[li, slot_rows, slot_pos].set(
                    kt_new.astype(ktp.dtype))
        rank_on = rcfg.mode != "off" and slot_ranks is not None
        with jax.named_scope("kv_read"):
            if not paged:
                vg = vp[li, page_table].reshape(ns, M, hkv, dh)
            if not paged and (not rank_on or ktp is None or mixed):
                kg = kp[li, page_table].reshape(ns, M, hkv, dh)
                # stale page contents (freed + re-issued pages) must not
                # leak: zero everything beyond the valid prefix
                k_dense = kg * valid[:, :, None, None].astype(kg.dtype)
            if ktp is not None:
                # the slot-indexed factors: r/d of the dense K bytes
                ktg = ktp[li, :ns]                    # (ns, M, hkv, r)
                k_fac = (ktg * valid[:, :, None, None].astype(ktg.dtype)
                         ).astype(x.dtype)
        if not rank_on:
            q_use, k_use = q, (None if paged else k_dense)
        elif paged:
            # the score against K as stored: q projected onto the basis,
            # masked to the slot's rank and projected back
            with jax.named_scope("rank_proj"):
                q_fac = jnp.einsum(
                    "bmgd,bgdr->bmgr",
                    group_q_heads(q.astype(jnp.float32), hkv),
                    basis_l) * col_ok[:, None, None, :]
                q_back = ungroup_q_heads(jnp.einsum(
                    "bmgr,bgdr->bmgd", q_fac, basis_l), C)
                q_use = (jnp.where(is_pf[:, None, None, None],
                                   q.astype(jnp.float32), q_back)
                         if mixed else q_back)
        else:
            # project q onto the slot's cached segment eigenbasis; per-row
            # rank = zeroed q columns beyond the slot's bucket (the score
            # contraction then ignores the matching k-factor columns, so
            # the k side needs no mask)
            with jax.named_scope("rank_proj"):
                # each kv head's basis serves its n_rep q heads in place
                q_proj = ungroup_q_heads(jnp.einsum(
                    "bmgd,bgdr->bmgr",
                    group_q_heads(q.astype(jnp.float32), hkv), basis_l), C)
                q_proj = (q_proj * col_ok[:, None, None, :]).astype(x.dtype)
                if ktp is None:
                    k_fac = jnp.einsum("bmhd,bhdr->bmhr",
                                       k_dense.astype(jnp.float32),
                                       basis_l).astype(x.dtype)
            if not mixed:
                q_use, k_use = q_proj, k_fac
            else:
                # mid-prefill rows attend full-rank dense; decode rows
                # keep the factor read. Pad the factor side to head-dim
                # width (exact zeros) and select per row.
                with jax.named_scope("kv_read"):
                    pad = ((0, 0), (0, 0), (0, 0), (0, dh - r_keep))
                    q_use = jnp.where(is_pf[:, None, None, None], q,
                                      jnp.pad(q_proj, pad))
                    k_use = jnp.where(is_pf[:, None, None, None], k_dense,
                                      jnp.pad(k_fac, pad))
        with jax.named_scope("attend"):
            probs = None
            want_probs = (mp is not None) or mass_defer
            if paged:
                from repro.kernels.ops import paged_attention
                res = paged_attention(
                    q_use, kp, vp, li, page_table, kv_end, slot_lens,
                    # a dead lane has no valid query: no page is read
                    jnp.where(active, q_lens, 0), scale=scale,
                    return_mass=mp is not None)
                o, w_tok = res if mp is not None else (res, None)
                o = o.astype(x.dtype)                        # (ns, C, hq, dh)
            elif use_kernel:
                from repro.kernels.ops import decode_attention
                qk = jnp.swapaxes(q_use, 1, 2)               # (ns, hq, C, r)
                res = decode_attention(
                    qk if mixed or C > 1 else qk[:, :, 0],
                    jnp.swapaxes(k_use, 1, 2),               # (ns, hkv, M, r)
                    jnp.swapaxes(vg, 1, 2),                  # (ns, hkv, M, dh)
                    kv_end, scale=scale, q_start=slot_lens,
                    return_probs=want_probs)
                if want_probs:
                    o, probs = res                       # probs (ns, hq, [C,] M)
                else:
                    o = res
                if o.ndim == 3:
                    o, probs = o[:, :, None], (None if probs is None
                                               else probs[:, :, None])
                o = jnp.swapaxes(o, 1, 2)                    # (ns, C, hq, dh)
            else:
                # grouped GQA: each kv head is read once for its n_rep
                # q heads, never repeated to hq heads
                res = attend(q_use, k_use, vg, scale=scale, causal=False,
                             kv_len=kv_len_q[:, None, :, None],
                             score_dtype=score_dtype,
                             return_probs=want_probs)
                if want_probs:
                    o, probs = res                           # probs (ns, hq, C, M)
                else:
                    o = res
        mass_q = None
        with jax.named_scope("mass"):
            if mp is not None:
                # per-key attention mass: group-mean over each kv head's q
                # heads, masked to live lanes and valid queries (dead lanes /
                # padding columns contribute exact zeros, so the slot-indexed
                # accumulate is a plain add — no scatter, no scratch row).
                # Cells appended this step are reset before the add, so a
                # recycled slot's stale mass dies the moment the position is
                # reused — no eager pool-wide zeroing at admission.
                from repro.models.common import kv_group_mean
                if not paged:
                    w = (probs.astype(jnp.float32)
                         * write_ok[:, None, :, None]).sum(axis=2)  # (ns, hq, M)
                    w_tok = kv_group_mean(w, hkv)                   # (ns, hkv, M)
                mp_l = (jnp.where(new_cell[:, :, None], 0.0, mp[li])
                        + jnp.swapaxes(w_tok, 1, 2).astype(mp.dtype))
                mp = jax.lax.dynamic_update_index_in_dim(mp, mp_l, li, 0)
            if mass_defer:
                # per-query mass, NOT summed over the chunk: the caller masks
                # to the accepted queries before applying (spec verify)
                from repro.models.common import kv_group_mean
                wq = (probs.astype(jnp.float32)
                      * write_ok[:, None, :, None])              # (ns, hq, C, M)
                wq = kv_group_mean(jnp.swapaxes(wq, 1, 2), hkv)  # (ns, C, hkv, M)
                mass_q = jnp.swapaxes(wq, 2, 3)                  # (ns, C, M, hkv)
        with jax.named_scope("out_proj"):
            x = x + jnp.einsum("bshf,hfd->bsd", o,
                               p["wo"].reshape(hq, dh, d).astype(x.dtype))
        with jax.named_scope("ffn"):
            if cfg.family == "moe" and cfg.moe is not None and "moe" in lp:
                f, _ = moe_mod.moe_ffn(cfg, lp["moe"],
                                       nn.rms_norm(x, lp["ln2"], cfg.rms_eps))
            else:
                f = nn.swiglu(nn.rms_norm(x, lp["ln2"], cfg.rms_eps),
                              lp["ffn"]["w_gate"], lp["ffn"]["w_up"],
                              lp["ffn"]["w_down"])
            x = x + f
        new_extra = {}
        if ktp is not None:
            new_extra["kt"] = ktp
        if mp is not None:
            new_extra["mass"] = mp
        ys = {} if mass_q is None else {"mass_q": mass_q}
        return (x, kp, vp, new_extra), ys

    from repro.models.common import scan_or_unroll
    with jax.named_scope("layers"):
        L = cfg.num_layers
        basis_xs = (basis if basis is not None else
                    jnp.zeros((L, ns, hkv, dh, 1), jnp.float32))
        extra = {}
        if kt_pool is not None:
            extra["kt"] = kt_pool
        if mass_pool is not None:
            extra["mass"] = mass_pool
        # a host array of layer ids: scanned it becomes the traced index,
        # unrolled each layer reads a static one
        (x, nk, nv, n_extra), ys = scan_or_unroll(
            body, (x, pool_k, pool_v, extra),
            (params["layers"], np.arange(L, dtype=np.int32), basis_xs),
            unroll=not cfg.scan_layers)
    with jax.named_scope("head"):
        if C > 1 and not return_all_logits:
            # only each row's last valid query feeds the LM head: the next
            # token for decode rows, token 0 for a row finishing its prompt
            x = jnp.take_along_axis(x, (q_lens - 1)[:, None, None], axis=1)
        x = nn.rms_norm(x, params["ln_f"], cfg.rms_eps)
        head = params.get("lm_head", None)
        logits = (jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
                  if head is not None else
                  jnp.einsum("bsd,vd->bsv", x,
                             params["embed"].astype(x.dtype)))
    pools = {"k": nk, "v": nv, **n_extra, **ys}
    return logits, pools
