"""Per-slot segment-level rank decision (slot-indexed, device-resident).

A jitted slot-indexed decision rather than a whole-batch host-side one:
the slot id is a traced scalar, so ONE executable serves every slot; the
spectral solve runs over that slot's live K view for all layers, the
guardrail veto and annealed threshold apply per slot — and crucially no
``int(cache["len"])`` host syncs: lengths, previous ranks, bases and
spectra live on device and the chosen rank / basis / factor pages are
written back with dynamic-index updates, feeding straight into the fused
decode step's rank masks.

The eigenbasis comes from the **softmax-weighted Gram** G = K^T diag(w) K,
with w the slot's accumulated per-key attention mass (seeded at prefill,
advanced in-graph by every decode step). The plain K Gram spends rank on
directions Q never looks at — the serve-time incarnation of the quality
gap the weighted basis already closed on the prefill path
(models/lowrank_cache.py:attention_mass). A slot whose mass accumulator is
all zero (direct cache writes in tests) falls back to uniform weights,
which is exactly the plain Gram.

Decision rules per slot (same semantics the lock-step server had):
  * kv_len < 8            -> r_max (too little signal; no veto)
  * mode == 'fixed'       -> fixed_rank
  * mode == 'adaptive'    -> NER-threshold rank per head, median over heads,
                             snapped to the compiled grid
  * mode == 'drrl'        -> policy logits per (slot, head) with the Eq. 11
                             safety mask, head-mean argmax per slot
  * mode == 'learned'     -> same inference path as 'drrl', loaded from a
                             checkpoint trained offline on recorded serving
                             traces (repro.train.serve_policy)
  * mode == 'random'      -> uniform grid draw keyed by (slot, clock)
  * transition veto       -> Eq. 9 relative bound at the chosen bucket vs
                             the slot's annealed eps_t, with the "before"
                             side taken from the slot's persisted
                             previous-segment spectra — the veto measures
                             the actual transition

When the cache runs in factor form, a decision also rewrites the slot's
``kt_pool`` pages as K . B_r under the refreshed basis, so the fused step
keeps reading consistent factors across the basis switch.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import lowrank as lr
from repro.core import perturbation as pert


def make_decide_fn(cfg: ModelConfig, policy_params=None) -> Callable:
    """Returns jitted ``decide(k_pool, mass_pool, kt_pool, page_table,
    lens, ranks, basis, spectra, slot, has_rank, t) -> (ranks', basis',
    spectra', kt_pool', vetoed)``.

    One call re-decides ONE slot (``slot`` is a traced scalar index — a
    single executable serves every slot): it gathers that slot's K and
    attention-mass pages, takes the weighted spectral solve for all
    layers, picks the rank bucket from the layer-0 spectra (same rules the
    old lock-step server used), applies the Eq. 9/11 transition veto
    against the slot's previous-segment spectra, and writes the slot's new
    rank, per-layer K eigenbasis, layer-0 spectra and (in factor form) its
    re-projected kt pages back into the device-resident state with
    dynamic-index updates. The fused decode step only *projects* onto the
    cached basis / reads the cached factors, so the eigh cost is paid once
    per segment, not once per token (paper Eq. 12's segment-level refresh)
    — and per-slot calls keep the spectral work proportional to the number
    of boundary crossings, exactly what a per-stream server would pay,
    instead of n_slots times the union.

    ``kt_pool`` may be None (dense-K serving): the returned kt_pool is
    then None as well. ``vetoed`` is a device bool scalar — True iff the
    Eq. 9 transition veto overrode the policy's choice this call. The
    engine banks it *unfetched* for export-time rank telemetry
    (repro.obs), so observing veto fires costs the loop nothing.
    """
    rcfg = cfg.rank
    if rcfg.mode == "off":
        raise ValueError("decide fn is undefined for rank mode 'off'")
    if rcfg.mode in ("drrl", "learned") and policy_params is None:
        # used to fall back silently to 'random' — a misconfigured policy
        # engine must fail at construction, not serve noise
        raise ValueError(
            f"rank mode {rcfg.mode!r} needs policy params: pass them as the "
            "third positional arg (ServeEngine(cfg, params, policy_params) "
            "/ Engine(cfg, params, policy_params, config=...)); 'learned' "
            "params come from repro.train.serve_policy.load_policy()")
    grid = jnp.asarray(rcfg.rank_grid, jnp.int32)
    g_lo, g_hi = int(rcfg.rank_grid[0]), int(rcfg.rank_grid[-1])
    dh = cfg.resolved_head_dim()
    r_keep = min(g_hi, dh)
    # donate the state this call rewrites (kt_pool especially — a full
    # K-sized pool copied per boundary crossing otherwise). ranks are NOT
    # donated: the engine's rank_history keeps references to past rank
    # arrays that a later decide would invalidate. CPU ignores donation
    # and warns, so donate on real accelerators only.
    donate = () if jax.default_backend() == "cpu" else (2, 6, 7)

    @functools.partial(jax.jit, donate_argnums=donate)
    def decide(k_pool, mass_pool, kt_pool, page_table, lens, ranks, basis,
               spectra, slot, has_rank, t):
        pt_row = jax.lax.dynamic_slice_in_dim(page_table, slot, 1, 0)[0]
        kv_len = jax.lax.dynamic_slice_in_dim(lens, slot, 1, 0)[0]
        prev_rank = jax.lax.dynamic_slice_in_dim(ranks, slot, 1, 0)[0]
        # a recycled slot's first decision must not see the previous
        # occupant's rank (the drrl feature path reads it even though the
        # veto is disabled): fall back to the fresh-slot default r_max
        prev_rank = jnp.where(has_rank, prev_rank, jnp.int32(g_hi))
        with jax.named_scope("gather_k"):
            gathered = k_pool[:, pt_row]       # (L, pages, ps, hkv * dh)
            L = gathered.shape[0]
            kv = gathered.reshape(L, -1, cfg.num_kv_heads, dh)
            M = kv.shape[1]
            valid = (jnp.arange(M) < kv_len).astype(jnp.float32)
            kk = jnp.swapaxes(kv, 1, 2).astype(jnp.float32) \
                * valid[None, None, :, None]              # (L, h, M, d)
        # softmax-weighted Gram: w is the accumulated per-key attention
        # mass, normalised to sum kv_len so the spectra stay on the plain
        # Gram's scale (weights 1 per key); zero mass (state written
        # outside the engine) degrades to uniform weights == plain Gram.
        # mass is slot-indexed (per-stream state, not per-page — shared
        # prefix pages receive different mass from each sharing slot), so
        # the gather is a plain row slice, no page indirection
        with jax.named_scope("gram"):
            w_row = jax.lax.dynamic_slice_in_dim(mass_pool, slot, 1, 1)[:, 0]
            w = jnp.swapaxes(w_row, 1, 2)                     # (L, h, M)
            w = jnp.maximum(w, 0.0) * valid[None, None, :]    # (L, h, M)
            tot = jnp.sum(w, axis=-1, keepdims=True)
            n_valid = jnp.maximum(kv_len.astype(jnp.float32), 1.0)
            w = jnp.where(tot > 0.0, w * n_valid / jnp.maximum(tot, 1e-30),
                          valid[None, None, :])
            gk = jnp.einsum("lhmd,lhm,lhme->lhde", kk, w, kk)
        with jax.named_scope("eigh"):
            s2_l, evecs_l = lr.gram_spectrum(gk)          # (L, h, d[, d])
        s2 = s2_l[0]                 # layer-0 spectra drive the decision
        h = s2.shape[0]
        eps_t = pert.annealed_threshold(rcfg.epsilon0, rcfg.anneal_lambda, t)
        # "before" side of the transition: the spectra persisted at the
        # slot's previous decision (first decision: no transition yet —
        # compare against itself, and the veto is disabled via has_rank)
        prev_s2 = jax.lax.dynamic_slice_in_dim(spectra, slot, 1, 0)[0]
        prev_s2 = jnp.where(has_rank, prev_s2, s2)

        if rcfg.mode == "fixed":
            chosen = jnp.int32(rcfg.fixed_rank)
        elif rcfg.mode == "adaptive":
            r = lr.rank_for_energy(s2, rcfg.energy_threshold, g_lo, g_hi)
            med = jnp.median(r.astype(jnp.float32))
            chosen = grid[jnp.argmin(jnp.abs(grid.astype(jnp.float32) - med))]
        elif rcfg.mode in ("drrl", "learned"):
            # 'learned' is the same device-resident inference path with
            # params trained offline on serving traces — the trainer
            # (repro.train.serve_policy) builds its features through this
            # very recipe (zero h_t/w_t, layer 0, spectra-only ctx), so
            # checkpointed params transfer without translation
            from repro.core.drrl import build_features
            from repro.core.policy import policy_apply
            h_t = jnp.zeros((1, 8), jnp.float32)
            w_t = jnp.zeros((9,), jnp.float32)
            prev = jnp.full((1, h), prev_rank, jnp.int32)
            ctx = {"k_s2": s2[None], "q_s2": prev_s2[None]}
            feats, (_, _, bounds_rel, _) = build_features(
                rcfg, ctx, h_t, w_t, 0, prev)
            logits, _ = policy_apply(policy_params, feats)     # (h, G)
            G = logits.shape[-1]
            ok = pert.safety_mask(bounds_rel.reshape(-1, G), eps_t)
            logits = jnp.where(ok, logits, -1e30)
            chosen = grid[jnp.argmax(jnp.mean(logits, axis=0))]
        else:                                     # 'random'
            # fold BOTH the slot id and its segment clock into the key:
            # folding only t made every slot at the same clock draw the
            # same bucket, and made draws repeat across runs
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(17),
                                   t.astype(jnp.int32)),
                slot.astype(jnp.int32))
            chosen = grid[jax.random.randint(key, (), 0, grid.shape[0])]

        # transition veto (Eq. 9): head-mean relative bound at the chosen
        # bucket must clear the slot's annealed threshold. The bound's dQ
        # side uses the previous-segment spectra, so it estimates the
        # actual segment-to-segment score perturbation.
        bounds, norm = pert.guardrail_report(prev_s2, s2, rcfg.rank_grid, dh)
        rel = jnp.mean(bounds / jnp.maximum(norm[..., None], 1e-30), axis=0)
        rel_c = rel[jnp.argmin(jnp.abs(grid - chosen))]
        switching = has_rank & (chosen != prev_rank)
        vetoed = switching & (rel_c > eps_t)
        chosen = jnp.where(vetoed, prev_rank, chosen)
        chosen = jnp.where(kv_len < 8, g_hi, chosen)
        # the short-context override is not a veto fire (docstring: "too
        # little signal; no veto")
        vetoed = vetoed & (kv_len >= 8)

        ranks = jax.lax.dynamic_update_slice_in_dim(
            ranks, chosen[None], slot, 0)
        basis = jax.lax.dynamic_update_slice(
            basis, evecs_l[:, None, :, :, :r_keep],
            (0, slot, 0, 0, 0))
        spectra = jax.lax.dynamic_update_slice(
            spectra, s2[None], (slot, 0, 0))
        if kt_pool is not None:
            # factor-form refresh: re-project the slot's whole K run onto
            # the new basis so the fused step's factor reads stay
            # consistent across the basis switch (positions beyond kv_len
            # are already zeroed in kk). kt is slot-indexed — the factors
            # depend on this slot's basis, so a shared prefix page's keys
            # are re-projected into the slot's OWN row, never into state
            # another slot reads
            with jax.named_scope("reproject"):
                kt = jnp.einsum("lhmd,lhdr->lmhr", kk,
                                evecs_l[..., :r_keep])
                kt_pool = jax.lax.dynamic_update_slice(
                    kt_pool, kt[:, None].astype(kt_pool.dtype),
                    (0, slot, 0, 0, 0))
        return ranks, basis, spectra, kt_pool, vetoed

    return decide


def draft_ranks(ranks: jnp.ndarray, spectra: jnp.ndarray, *,
                frac: float, grid_lo: int, r_cap: int,
                energy: float = 0.5) -> jnp.ndarray:
    """Per-slot draft rank for self-speculative decoding: (n_slots,) int32.

    The draft forward reads the factor cache at an aggressive fraction of
    each slot's current rank (``ceil(frac * rank)``), floor-clamped by the
    slot's own cached layer-0 spectra: a slot whose spectral mass is NOT
    concentrated never drafts below the rank that retains ``energy`` of it
    (head max — conservative), and never below the policy grid's floor
    ``grid_lo``. ``r_cap`` is the static draft width the engine sliced the
    basis/factor pools to, so the result is always representable there.
    Fresh slots with all-zero spectra (no decision yet, or state written
    directly in tests) degrade to the grid floor. Never exceeds the slot's
    current rank: the draft is a strictly cheaper read of the same basis.
    """
    r_e = lr.rank_for_energy(spectra, energy, 1, r_cap)   # (ns, hkv)
    has_sig = jnp.any(spectra > 0.0, axis=(1, 2))         # (ns,)
    floor = jnp.where(has_sig, jnp.max(r_e, axis=1), grid_lo)
    floor = jnp.clip(floor, grid_lo, r_cap)
    rd = jnp.ceil(frac * ranks.astype(jnp.float32)).astype(jnp.int32)
    rd = jnp.maximum(rd, floor.astype(jnp.int32))
    return jnp.minimum(jnp.minimum(rd, jnp.int32(r_cap)), ranks)


def basis_drift(k_tok: jnp.ndarray, basis: jnp.ndarray,
                ranks: jnp.ndarray) -> jnp.ndarray:
    """Residual energy of the newest K token outside each slot's stored
    layer-0 eigenbasis (first ``rank`` columns): (n_slots,) in [0, 1]. High
    drift means the segment's subspace went stale — the engine can trigger
    an early re-decision instead of waiting out the segment.

    k_tok: (n_slots, hkv, dh); basis: (n_slots, hkv, dh, r_keep)."""
    r_keep = basis.shape[-1]
    col_ok = (jnp.arange(r_keep)[None, :]
              < jnp.minimum(ranks[:, None], r_keep)).astype(jnp.float32)
    b = basis * col_ok[:, None, None, :]
    kf = k_tok.astype(jnp.float32)
    proj = jnp.einsum("shd,shdr,sher->she", kf, b, b)
    num = jnp.sum((kf - proj) ** 2, axis=(1, 2))
    den = jnp.maximum(jnp.sum(kf ** 2, axis=(1, 2)), 1e-30)
    return num / den
