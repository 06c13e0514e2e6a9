"""Grouped GQA attention.

``attend`` given fewer kv heads than q heads contracts per kv group and
must equal attention over K/V repeated to the q heads, outputs and probs
alike, in repeat_kv's head order. The fused paged step passes the cache's
kv heads to it un-repeated, so a GQA model's step must equal the step of
the MHA model that repeats its K/V weights, pools and bases."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import RankConfig
from repro.models.api import get_model
from repro.models.attention import attend
from repro.models.common import (group_q_heads, kv_group_mean, repeat_kv,
                                 ungroup_q_heads)

K0 = jax.random.PRNGKey(0)


@pytest.mark.parametrize("score_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("sq", [1, 16])
@pytest.mark.parametrize("n_rep", [1, 2, 6])
def test_grouped_attend_equals_repeated(n_rep, sq, score_dtype):
    b, skv, hkv, dq, dv = 3, 40, 2, 8, 16
    hq = hkv * n_rep
    ks = jax.random.split(jax.random.fold_in(K0, n_rep * 100 + sq), 3)
    q = jax.random.normal(ks[0], (b, sq, hq, dq))
    k = jax.random.normal(ks[1], (b, skv, hkv, dq))
    v = jax.random.normal(ks[2], (b, skv, hkv, dv))
    # per-(row, query) visible lengths, as the fused step passes them
    start = jnp.asarray([5, 17, skv - sq])
    kv_len = (start[:, None] + jnp.arange(sq)[None, :] + 1)[:, None, :, None]
    kw = dict(scale=dq ** -0.5, causal=False, kv_len=kv_len,
              score_dtype=score_dtype, return_probs=True)
    out, probs = attend(q, k, v, **kw)
    ref_out, ref_probs = attend(q, repeat_kv(k, n_rep), repeat_kv(v, n_rep),
                                **kw)
    assert out.shape == (b, sq, hq, dv)
    assert probs.shape == (b, hq, sq, skv)
    tol = 1e-5 if score_dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_out, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(probs, np.float32),
                               np.asarray(ref_probs, np.float32),
                               atol=tol, rtol=tol)
    # masked positions get no mass, and every kv group's mean matches
    assert float(jnp.where(jnp.arange(skv) >= kv_len, probs, 0).max()) == 0
    np.testing.assert_allclose(np.asarray(kv_group_mean(probs[:, :, 0], hkv)),
                               np.asarray(kv_group_mean(ref_probs[:, :, 0],
                                                        hkv)),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("n_rep", [1, 2, 6])
def test_group_q_heads_rows_follow_repeat_kv(n_rep):
    """Row n * s + i of kv group g holds q head g * n_rep + n at position
    i, the head repeat_kv gives kv head g; ungrouping restores q."""
    b, s, hkv, d = 2, 5, 3, 4
    q = jax.random.normal(K0, (b, s, hkv * n_rep, d))
    g = group_q_heads(q, hkv)
    assert g.shape == (b, n_rep * s, hkv, d)
    for gi in range(hkv):
        for n in range(n_rep):
            np.testing.assert_array_equal(
                np.asarray(g[:, n * s:(n + 1) * s, gi]),
                np.asarray(q[:, :, gi * n_rep + n]))
    np.testing.assert_array_equal(np.asarray(ungroup_q_heads(g, s)),
                                  np.asarray(q))


def test_grouped_attend_causal_and_chunked_guard():
    ks = jax.random.split(K0, 3)
    q = jax.random.normal(ks[0], (2, 12, 6, 8))
    k = jax.random.normal(ks[1], (2, 12, 2, 8))
    v = jax.random.normal(ks[2], (2, 12, 2, 8))
    kw = dict(scale=0.3, causal=True, q_offset=0)
    np.testing.assert_allclose(
        np.asarray(attend(q, k, v, **kw)),
        np.asarray(attend(q, repeat_kv(k, 3), repeat_kv(v, 3), **kw)),
        atol=1e-5, rtol=1e-5)
    with pytest.raises(ValueError, match="chunked"):
        attend(q, k, v, chunked=True, chunk=4, **kw)
    with pytest.raises(ValueError, match="divide"):
        attend(q, k[:, :, :1].repeat(4, axis=2), v[:, :, :1].repeat(4, axis=2),
               **kw)


def _repeat_flat(x, hkv, dh, n_rep):
    """(..., hkv * dh) -> (..., hq * dh), each kv head copied to its n_rep
    q heads in repeat_kv's order (kv head g -> heads g * n_rep + n)."""
    return jnp.repeat(x.reshape(*x.shape[:-1], hkv, dh), n_rep, axis=-2
                      ).reshape(*x.shape[:-1], -1)


def _as_mha(params, hkv, dh, n_rep):
    """The MHA twin of GQA weights: each kv head's K/V projection copied
    to its n_rep q heads."""
    attn = dict(params["layers"]["attn"])
    for name in ("wk", "wv", "bk", "bv"):
        if name in attn:
            attn[name] = _repeat_flat(attn[name], hkv, dh, n_rep)
    return {**params, "layers": {**params["layers"], "attn": attn}}


@pytest.mark.parametrize("form", ["decode", "decode_kt", "mixed", "defer"])
def test_gqa_fused_step_equals_repeated_mha(form):
    """The fused paged step of a GQA model (kv heads un-repeated into the
    grouped contraction and the grouped q projection) equals the step of
    its MHA twin: logits, written pools, factors and attention mass."""
    n_rep = 2
    base = get_config("drrl-paper", reduced=True)
    rank = RankConfig(mode="fixed", rank_grid=(4, 8, 12), fixed_rank=8)
    hq, dh, L = base.num_heads, base.resolved_head_dim(), base.num_layers
    hkv = hq // n_rep
    gqa = base.with_(num_kv_heads=hkv, rank=rank)
    mha = base.with_(num_kv_heads=hq, rank=rank)
    fns_g, fns_m = get_model(gqa), get_model(mha)
    params = fns_g.init(K0)
    params_m = _as_mha(params, hkv, dh, n_rep)
    ns, ps, n_pp, r = 3, 4, 4, 12
    M = ps * n_pp
    C = 1 if form.startswith("decode") else 4
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    P = ns * n_pp + 1
    pk = jax.random.normal(ks[0], (L, P, ps, hkv * dh))
    pv = jax.random.normal(ks[1], (L, P, ps, hkv * dh))
    table = jnp.arange(1, P, dtype=jnp.int32).reshape(ns, n_pp)
    toks = jax.random.randint(ks[2], (ns, C), 0, gqa.vocab_size)
    lens = jnp.asarray([3, 9, 6], jnp.int32)
    ranks = jnp.asarray([4, 8, 12], jnp.int32)
    basis = jax.random.normal(ks[3], (L, ns, hkv, dh, r))
    mass = jax.random.uniform(ks[4], (L, ns, M, hkv))

    def rep(x, axis):
        return jnp.repeat(x, n_rep, axis=axis)

    def flat_rep(pool):
        return _repeat_flat(pool, hkv, dh, n_rep)

    kw_g = dict(slot_lens=lens, slot_ranks=ranks, basis=basis)
    kw_m = dict(slot_lens=lens, slot_ranks=ranks, basis=rep(basis, 2))
    if form == "defer":
        kw_g.update(mass_defer=True, return_all_logits=True)
        kw_m.update(mass_defer=True, return_all_logits=True)
    else:
        kw_g["mass_pool"], kw_m["mass_pool"] = mass, rep(mass, 3)
    if form == "decode_kt":
        kt = jax.random.normal(ks[5], (L, ns + 1, M, hkv, r))
        kw_g["kt_pool"], kw_m["kt_pool"] = kt, rep(kt, 3)
    if C > 1:
        q_lens = jnp.asarray([1, 4, 2], jnp.int32)
        kw_g["q_lens"] = kw_m["q_lens"] = q_lens
        if form == "mixed":
            pf = jnp.asarray([False, True, False])
            kw_g["prefill_rows"] = kw_m["prefill_rows"] = pf
    lg, pools = fns_g.decode_step_paged(params, pk, pv, table, toks, **kw_g)
    lm, pools_m = fns_m.decode_step_paged(params_m, flat_rep(pk),
                                          flat_rep(pv), table, toks, **kw_m)
    tol = dict(atol=2e-4, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lm), **tol)
    for name in ("k", "v"):
        np.testing.assert_allclose(np.asarray(flat_rep(pools[name])),
                                   np.asarray(pools_m[name]), **tol)
    if "kt" in pools:
        np.testing.assert_allclose(np.asarray(rep(pools["kt"], 3)),
                                   np.asarray(pools_m["kt"]), **tol)
    mkey = "mass_q" if form == "defer" else "mass"
    mq = pools_m[mkey]
    np.testing.assert_allclose(
        np.asarray(pools[mkey]),
        np.asarray(mq.reshape(*mq.shape[:-1], hkv, n_rep).mean(-1)), **tol)
