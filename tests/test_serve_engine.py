"""Continuous-batching serving engine (repro.serve).

Covers the three layers separately and end-to-end:
  * scheduler admission/eviction invariants (property-based),
  * slot-paged KV cache write/gather round-trips and page accounting,
  * fused paged decode == monolithic decode (mode 'off'),
  * per-row rank masking == whole-batch static rank factors,
  * the acceptance parity run: >= 3 staggered heterogeneous streams decode
    token-identically to each stream served alone by a 1-slot engine
    while two distinct rank buckets are live in one fused step.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.configs.base import RankConfig
from repro.models.api import get_model
from repro.serve import (EngineConfig, PagedKVCache, Request, Scheduler,
                         ServeEngine)
from repro.serve.kv_cache import gather_views


pytestmark = pytest.mark.serve

RNG = jax.random.PRNGKey(0)


def _cfg(mode="off", seg=8):
    cfg = get_config("drrl-paper", reduced=True)
    return cfg.with_(rank=RankConfig(mode=mode, rank_grid=(4, 8, 12, 16),
                                     fixed_rank=8, segment_len=seg))


# ---------------------------------------------------------------------------
# scheduler invariants
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2 ** 16 - 1), st.integers(1, 4), st.integers(1, 12))
def test_scheduler_invariants(seed, n_slots, n_reqs):
    """Random workload through admit/evict: slots never double-booked, pages
    of live slots stay disjoint, FIFO admission order, everything finishes."""
    rnd = np.random.default_rng(seed)
    cfg = _cfg()
    cache = PagedKVCache(cfg, n_slots, max_len=32, page_size=8)
    sched = Scheduler(n_slots)
    reqs = [Request(rid=i, tokens=rnd.integers(0, 99, rnd.integers(1, 13)),
                    max_new=int(rnd.integers(1, 8)),
                    arrival=int(rnd.integers(0, 6)))
            for i in range(n_reqs)]
    for r in reqs:
        sched.submit(r)
    admitted_order = []
    for now in range(200):
        placed = sched.admit(now, cache.allocate)
        for slot, req in placed:
            assert req.arrival <= now
            admitted_order.append(req.rid)
            cache.lens[slot] = len(req.tokens)
            sched.slots[slot].prefilled = len(req.tokens)  # prompt consumed
            sched.slots[slot].n_out = 1
        # invariant: one live request per slot, disjoint live pages
        live = [s.req.rid for s in sched.slots if s.active]
        assert len(live) == len(set(live))
        pages = [p for row in cache.live_pages().values() for p in row]
        assert len(pages) == len(set(pages)) and 0 not in pages
        # decode tick: every live slot emits one token, then evict
        for i, stt in enumerate(sched.slots):
            if stt.active:
                stt.decode_i += 1
                stt.n_out += 1
                cache.lens[i] += 1
            if stt.active and sched.should_evict(i):
                sched.evict(i, cache.release, list(range(stt.n_out)))
        if sched.done():
            break
    assert sched.done()
    assert sorted(r for r, _ in
                  [(rq.rid, o) for rq, o in sched.finished]) == sorted(
                      r.rid for r in reqs)
    # FIFO: requests with earlier arrival among the same admission window
    # never overtake — admitted order is sorted by (arrival, rid) per wave
    assert len(admitted_order) == n_reqs
    # all pages returned to the pool at the end
    assert cache.free_pages == cache.n_pages - 1


def test_scheduler_rejects_oversized_and_blocks_fifo():
    cfg = _cfg()
    cache = PagedKVCache(cfg, 2, max_len=16, page_size=8)
    sched = Scheduler(2)
    big = Request(rid=0, tokens=np.arange(10), max_new=10)   # needs 20 > 16
    sched.submit(big)
    sched.submit(Request(rid=1, tokens=np.arange(4), max_new=2))
    placed = sched.admit(0, cache.allocate)
    # head-of-queue can't be placed -> FIFO blocks the whole queue
    assert placed == [] and len(sched.pending) == 2


# ---------------------------------------------------------------------------
# paged cache round-trip
# ---------------------------------------------------------------------------

def test_paged_cache_roundtrip_and_release():
    cfg = _cfg()
    cache = PagedKVCache(cfg, n_slots=3, max_len=24, page_size=8)
    L, hkv, dh = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim()
    rnd = np.random.default_rng(0)
    written = {}
    for slot, s in ((0, 5), (1, 24), (2, 9)):
        assert cache.allocate(slot, s)
        k = rnd.normal(size=(L, s, hkv, dh)).astype(np.float32)
        v = rnd.normal(size=(L, s, hkv, dh)).astype(np.float32)
        cache.write_prefill(slot, jnp.asarray(k), jnp.asarray(v))
        written[slot] = (k, v, s)
    kv_all, vv_all = gather_views(cache.k_pool, cache.v_pool,
                                  jnp.asarray(cache.page_table), hkv)
    for slot, (k, v, s) in written.items():
        kg, vg = cache.gather_slot(slot)
        np.testing.assert_array_equal(np.asarray(kg[:, :s]), k)
        np.testing.assert_array_equal(np.asarray(vg[:, :s]), v)
        np.testing.assert_array_equal(np.asarray(kv_all[:, slot, :s]), k)
        np.testing.assert_array_equal(np.asarray(vv_all[:, slot, :s]), v)
        assert int(cache.lens[slot]) == s
    # release returns pages; a fresh allocation can reuse them
    free0 = cache.free_pages
    cache.release(1)
    assert cache.free_pages == free0 + cache.pages_needed(24)
    assert cache.allocate(1, 16)


@pytest.mark.parametrize("scan_layers", [True, False])
def test_mixed_step_writes_pools_in_place(scan_layers):
    """One mixed step (a decode row, a prefill row, a dead lane) over pools
    full of random values: the step writes each new token's K/V at its
    (layer, page, offset) and leaves every other entry bitwise as it was
    (page 0 is the scratch page that absorbs dead-lane writes)."""
    cfg = _cfg("off").with_(scan_layers=scan_layers)
    fns = get_model(cfg)
    params = fns.init(RNG)
    L, hkv, dh = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim()
    C, s0, ps = 4, 9, 8
    cache = PagedKVCache(cfg, n_slots=3, max_len=24, page_size=ps)
    rnd = np.random.default_rng(1)
    cache.k_pool = jnp.asarray(
        rnd.normal(size=cache.k_pool.shape).astype(np.float32))
    cache.v_pool = jnp.asarray(
        rnd.normal(size=cache.v_pool.shape).astype(np.float32))
    toks = jax.random.randint(jax.random.PRNGKey(7), (2, 16), 0,
                              cfg.vocab_size)
    # slot 0 decodes after s0 prompt tokens; slot 1 prefills its first
    # chunk; slot 2 is a dead lane
    mono0 = fns.init_cache(1, 24)
    _, mono0 = fns.decode_step(params, mono0, toks[0:1, :s0])
    assert cache.allocate(0, s0 + 1) and cache.allocate(1, C)
    cache.write_prefill(0, mono0["k"][:, 0, :s0], mono0["v"][:, 0, :s0])
    kg, vg = cache.gather_slot(0)
    kv_all, vv_all = gather_views(cache.k_pool, cache.v_pool,
                                  jnp.asarray(cache.page_table), hkv)
    for got in (kg, kv_all[:, 0]):
        np.testing.assert_array_equal(np.asarray(got[:, :s0]),
                                      np.asarray(mono0["k"][:, 0, :s0]))
    for got in (vg, vv_all[:, 0]):
        np.testing.assert_array_equal(np.asarray(got[:, :s0]),
                                      np.asarray(mono0["v"][:, 0, :s0]))
    k_in, v_in = np.asarray(cache.k_pool), np.asarray(cache.v_pool)
    tokens = jnp.zeros((3, C), jnp.int32)
    tokens = tokens.at[0, 0].set(toks[0, s0]).at[1].set(toks[1, :C])
    _, pools = jax.jit(fns.decode_step_paged)(
        params, cache.k_pool, cache.v_pool, jnp.asarray(cache.page_table),
        tokens, slot_lens=jnp.asarray([s0, 0, 0], jnp.int32),
        active=jnp.asarray([True, True, False]),
        q_lens=jnp.asarray([1, C, 1], jnp.int32),
        prefill_rows=jnp.asarray([False, True, False]))
    k_out, v_out = np.asarray(pools["k"]), np.asarray(pools["v"])
    assert k_out.shape == k_in.shape == (L, cache.n_pages, ps, hkv * dh)
    # the step's own K/V of the new tokens, from the monolithic decode
    _, mono0 = fns.decode_step(params, mono0, toks[0:1, s0:s0 + 1])
    _, mono1 = fns.decode_step(params, fns.init_cache(1, 24),
                               toks[1:2, :C])
    pt = cache.page_table
    coords = [(pt[0, s0 // ps], s0 % ps)] + [(pt[1, j // ps], j % ps)
                                             for j in range(C)]
    want_k = [mono0["k"][:, 0, s0]] + [mono1["k"][:, 0, j] for j in range(C)]
    want_v = [mono0["v"][:, 0, s0]] + [mono1["v"][:, 0, j] for j in range(C)]
    written = np.zeros((cache.n_pages, ps), bool)
    for (page, off), wk, wv in zip(coords, want_k, want_v):
        written[page, off] = True
        np.testing.assert_allclose(k_out[:, page, off],
                                   np.asarray(wk).reshape(L, -1),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(v_out[:, page, off],
                                   np.asarray(wv).reshape(L, -1),
                                   atol=1e-5, rtol=1e-5)
    kept = ~written
    kept[0] = False                                  # the scratch page
    np.testing.assert_array_equal(k_out[:, kept], k_in[:, kept])
    np.testing.assert_array_equal(v_out[:, kept], v_in[:, kept])


# ---------------------------------------------------------------------------
# fused paged decode == monolithic decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_kv", [None, 2], ids=["mha", "gqa"])
def test_paged_step_matches_monolithic_decode(n_kv):
    """Mode 'off': the fused per-row step must reproduce decode_step_dense
    for each slot independently, including slots at different lengths.
    The GQA case (2 kv heads for 4 q heads) checks the step's grouped
    contraction against the monolithic decode, which repeats K/V."""
    cfg = _cfg("off")
    if n_kv is not None:
        cfg = cfg.with_(num_kv_heads=n_kv)
        assert n_kv < cfg.num_heads
    fns = get_model(cfg)
    params = fns.init(RNG)
    lens = [6, 11]
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 12), 0,
                              cfg.vocab_size)
    cache = PagedKVCache(cfg, n_slots=2, max_len=16, page_size=8)
    refs = []
    for slot, s in enumerate(lens):
        mono = fns.init_cache(1, 16)
        _, mono = fns.decode_step(params, mono, toks[slot:slot + 1, :s])
        cache.allocate(slot, s + 1)
        cache.write_prefill(slot, mono["k"][:, 0, :s], mono["v"][:, 0, :s])
        lg, _ = fns.decode_step(params, mono, toks[slot:slot + 1, -1:])
        refs.append(np.asarray(lg[0]))
    logits, _ = fns.decode_step_paged(
        params, cache.k_pool, cache.v_pool, jnp.asarray(cache.page_table),
        jnp.stack([toks[0, -1:], toks[1, -1:]]),
        slot_lens=jnp.asarray(cache.lens, jnp.int32))
    for slot in range(2):
        np.testing.assert_allclose(np.asarray(logits[slot]), refs[slot],
                                   atol=2e-4, rtol=2e-3)


def test_per_row_rank_masking_equals_truncated_basis():
    """Zeroing basis columns beyond each row's rank must give the same
    scores as actually slicing the basis to r columns (factor padding +
    rank masking only ever adds exact 0.0 terms to the contraction)."""
    from repro.core import lowrank as lr
    b, m, h, d, r_max = 3, 12, 2, 16, 12
    ks = jax.random.split(RNG, 2)
    q = jax.random.normal(ks[0], (b, 1, h, d))
    k = jax.random.normal(ks[1], (b, m, h, d))
    _, evecs = lr.gram_spectrum(lr.gram(jnp.swapaxes(k, 1, 2)))
    basis = evecs[..., :r_max]                       # (b, h, d, r_max)
    ranks = jnp.asarray([4, 8, 12], jnp.int32)
    col_ok = (jnp.arange(r_max)[None, :] < ranks[:, None]).astype(jnp.float32)
    bm = basis * col_ok[:, None, None, :]
    q_m = jnp.einsum("bshd,bhdr->bshr", q, bm)
    k_m = jnp.einsum("bmhd,bhdr->bmhr", k, bm)
    sc_m = jnp.einsum("bshr,bmhr->bshm", q_m, k_m)
    for i, r in enumerate([4, 8, 12]):
        bs = basis[i:i + 1, ..., :r]
        q_s = jnp.einsum("bshd,bhdr->bshr", q[i:i + 1], bs)
        k_s = jnp.einsum("bmhd,bhdr->bmhr", k[i:i + 1], bs)
        sc_s = jnp.einsum("bshr,bmhr->bshm", q_s, k_s)
        np.testing.assert_allclose(np.asarray(sc_m[i:i + 1]),
                                   np.asarray(sc_s), atol=1e-4, rtol=1e-4)


def test_decide_matches_numpy_oracle():
    """Independent oracle for the slot-indexed rank decision: the adaptive
    rule (NER threshold per head -> median -> grid snap) recomputed in
    plain NumPy must agree, and the refreshed basis must be orthonormal
    while the other slot's state stays untouched."""
    from repro.serve.policy import make_decide_fn
    cfg = _cfg("adaptive")
    decide = make_decide_fn(cfg)
    cache = PagedKVCache(cfg, 2, max_len=16, page_size=8)
    L, hkv, dh = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim()
    rnd = np.random.default_rng(3)
    s = 12
    k = rnd.normal(size=(L, s, hkv, dh)).astype(np.float32)
    cache.allocate(0, s)
    cache.write_prefill(0, jnp.asarray(k), jnp.asarray(np.zeros_like(k)))
    ranks, basis, spectra, _, _veto = decide(
        cache.k_pool, cache.mass_pool, cache.kt_pool,
        jnp.asarray(cache.page_table),
        jnp.asarray(cache.lens, jnp.int32), cache.ranks,
        cache.basis, cache.spectra, np.int32(0), np.bool_(False),
        np.int32(0))
    grid = np.asarray(cfg.rank.rank_grid)
    g = np.einsum("shd,she->hde", k[0], k[0])   # (hkv, dh, dh) layer-0 Gram
    evals = np.linalg.eigvalsh(g)[..., ::-1]
    ner = np.cumsum(evals, -1) / evals.sum(-1, keepdims=True)
    met = (ner >= cfg.rank.energy_threshold).any(-1)
    r = np.where(met, 1 + np.argmax(ner >= cfg.rank.energy_threshold, -1),
                 grid[-1])
    r = np.clip(r, grid[0], grid[-1])
    expect = grid[np.argmin(np.abs(grid - np.median(r)))]
    assert int(ranks[0]) == int(expect)
    # refreshed basis: orthonormal columns per (layer, head)
    b = np.asarray(basis[:, 0])              # (L, hkv, dh, r_keep)
    btb = np.einsum("lhdr,lhds->lhrs", b, b)
    eye = np.broadcast_to(np.eye(b.shape[-1]), btb.shape)
    np.testing.assert_allclose(btb, eye, atol=1e-4)
    # slot 1 untouched by the dynamic-index update
    assert int(ranks[1]) == int(cache.ranks[1])
    assert float(jnp.abs(basis[:, 1]).max()) == 0.0
    # the decision persisted its layer-0 spectra (veto "before" side);
    # zero mass falls back to the plain Gram, so they match the oracle
    np.testing.assert_allclose(np.asarray(spectra[0]),
                               np.maximum(evals, 0.0), rtol=1e-4,
                               atol=1e-3 * float(evals.max()))
    assert float(jnp.abs(spectra[1]).max()) == 0.0


def test_fullrank_basis_projection_matches_off():
    """Independent check of the rank path: projecting onto a full-rank
    (r = dh) eigenbasis must reproduce the unprojected mode-'off' logits —
    the projection plumbing cannot change the math at full rank."""
    from repro.core import lowrank as lr
    cfg = _cfg("adaptive")                   # grid top 16 == dh
    fns = get_model(cfg)
    params = fns.init(RNG)
    dh = cfg.resolved_head_dim()
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 12), 0,
                              cfg.vocab_size)
    cache = PagedKVCache(cfg, n_slots=2, max_len=16, page_size=8)
    pf = get_model(cfg.with_(rank=cfg.rank.__class__(mode="off")))
    for slot, s in enumerate((6, 11)):
        mono = pf.init_cache(1, 16)
        _, mono = pf.decode_step(params, mono, toks[slot:slot + 1, :s])
        cache.allocate(slot, s + 1)
        cache.write_prefill(slot, mono["k"][:, 0, :s], mono["v"][:, 0, :s])
    kv_all, _ = gather_views(cache.k_pool, cache.v_pool,
                             jnp.asarray(cache.page_table),
                             cfg.num_kv_heads)
    lens = jnp.asarray(cache.lens, jnp.int32)
    valid = jnp.arange(kv_all.shape[2])[None, :] < lens[:, None]
    kk = (jnp.swapaxes(kv_all, 2, 3)
          * valid[None, :, None, :, None])
    _, evecs = lr.gram_spectrum(lr.gram(kk))
    args = (params, cache.k_pool, cache.v_pool,
            jnp.asarray(cache.page_table),
            jnp.stack([toks[0, -1:], toks[1, -1:]]))
    lg_off, _ = fns.decode_step_paged(*args, slot_lens=lens)
    lg_proj, _ = fns.decode_step_paged(
        *args, slot_lens=lens, slot_ranks=jnp.full((2,), dh, jnp.int32),
        basis=evecs[..., :dh])
    np.testing.assert_allclose(np.asarray(lg_proj), np.asarray(lg_off),
                               atol=2e-4, rtol=2e-3)


# ---------------------------------------------------------------------------
# the acceptance run: staggered heterogeneous streams, token parity
# ---------------------------------------------------------------------------

def test_engine_parity_staggered_streams():
    from repro.serve import Engine, SamplingParams
    cfg = _cfg("adaptive", seg=8)
    fns = get_model(cfg)
    params = fns.init(RNG)
    rnd = np.random.default_rng(0)
    prompts = [
        np.full((12,), 7, np.int32),                   # low-spectral prompt
        rnd.integers(0, cfg.vocab_size, 20).astype(np.int32),
        rnd.integers(0, cfg.vocab_size, 9).astype(np.int32),
        rnd.integers(0, cfg.vocab_size, 15).astype(np.int32),
    ]
    N = 16
    # 4 requests through 3 slots: the 4th stream rides a recycled slot,
    # so stale-page masking is on the line too. The default chunk (16)
    # splits the 20-token prompt.
    eng = ServeEngine(cfg, params, config=EngineConfig(
        n_slots=3, max_len=64, page_size=8, segment_len=8, max_new_cap=N))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, tokens=p, max_new=N, arrival=2 * i))
    eng.warmup()
    outs = eng.run()
    assert eng.stats["compile_s"] > 0.0
    assert eng.stats["prefills"] == len(prompts)

    # at least two distinct rank buckets live in one fused step
    per_step = eng.ranks_per_step()
    distinct = max(len({r for r in step.tolist() if r >= 0})
                   for step in per_step)
    assert distinct >= 2, per_step

    # token-for-token parity with each stream served alone, its whole
    # prompt in one covering chunk
    solo = Engine(cfg, params, config=EngineConfig(
        n_slots=1, max_len=64, page_size=8, segment_len=8, max_new_cap=N,
        prefill_chunk=max(len(p) for p in prompts)))
    for i, p in enumerate(prompts):
        solo.reset()
        h = solo.submit(p, SamplingParams(max_new=N))
        solo.run()
        np.testing.assert_array_equal(
            outs[i], h.result(),
            err_msg=f"stream {i} diverged from the 1-slot engine")


def test_engine_drift_trigger_forces_redecisions():
    """drift_threshold=0 makes every post-decision step re-decide (any
    nonzero residual trips it), so the decide count must exceed the
    segment-schedule count of an identical run without the trigger."""
    cfg = _cfg("adaptive", seg=8)
    fns = get_model(cfg)
    params = fns.init(RNG)
    prompt = np.arange(10, dtype=np.int32)

    def go(drift):
        eng = ServeEngine(cfg, params, config=EngineConfig(
            n_slots=1, max_len=48, page_size=8, segment_len=8, max_new_cap=12,
            drift_threshold=drift))
        eng.submit(Request(rid=0, tokens=prompt, max_new=12))
        outs = eng.run()
        return outs[0], eng.stats["decides"]

    out_base, n_base = go(None)
    out_drift, n_drift = go(0.0)
    assert n_drift > n_base
    assert out_drift.shape == out_base.shape


def test_engine_eos_eviction():
    """A stream whose request carries eos_id stops early and frees its slot."""
    cfg = _cfg("off")
    fns = get_model(cfg)
    params = fns.init(RNG)
    prompt = np.arange(8, dtype=np.int32)
    eng = ServeEngine(cfg, params, config=EngineConfig(
        n_slots=1, max_len=48, page_size=8, max_new_cap=12))
    eng.submit(Request(rid=0, tokens=prompt, max_new=12))
    outs = eng.run()
    full = outs[0]
    assert full.shape == (12,)
    # re-run with eos at whatever the 4th token was: must stop at its
    # first occurrence (which may be earlier)
    eos = int(full[3])
    stop = int(np.argmax(full == eos)) + 1
    eng2 = ServeEngine(cfg, params, config=EngineConfig(
        n_slots=1, max_len=48, page_size=8, max_new_cap=12))
    eng2.submit(Request(rid=0, tokens=prompt, max_new=12, eos_id=eos))
    outs2 = eng2.run()
    assert outs2[0].tolist() == full[:stop].tolist()
