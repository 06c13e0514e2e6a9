"""Shared-prefix KV reuse (repro.serve.prefix).

Covers the subsystem bottom-up:
  * radix-tree property tests (vendored-hypothesis fallback compatible):
    insert/match/evict round-trips against a real refcounted page pool —
    matches are true prefixes snapped to exact reuse points, refcounts
    never go negative, and zero live references <=> page reclaimable,
  * refcount/COW unit behaviour: page-aligned vs prompt-end reuse
    points, duplicate prompts snapping down a page, split invalidation,
  * the acceptance property: prefix-hit admission is token-for-token
    identical to cold admission across dense/factor x kernel/XLA, with
    recycled slots, copy-on-write tail pages and LRU eviction pressure
    on the line, and no page leaks under refcounting,
  * Engine.reset() clears the tree; EngineConfig validation.
"""
import threading

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.configs.base import RankConfig
from repro.models.api import get_model
from repro.serve import PagedKVCache, PrefixCache, Request, ServeEngine
from repro.serve.api import Engine, EngineConfig, SamplingParams


pytestmark = pytest.mark.serve

RNG = jax.random.PRNGKey(0)


def _cfg(mode="adaptive", **kw):
    cfg = get_config("drrl-paper", reduced=True)
    return cfg.with_(rank=RankConfig(mode=mode, rank_grid=(4, 8, 12, 16),
                                     fixed_rank=16, segment_len=8, **kw))


# ---------------------------------------------------------------------------
# radix tree + refcount property tests (host control plane only)
# ---------------------------------------------------------------------------

def _aligned_snaps(p_len, ps):
    """The snapshot positions the engine would capture with chunk == ps:
    every page boundary inside the prompt, plus the prompt end."""
    pts = {pos: None for pos in range(ps, p_len, ps)}
    pts[p_len] = None
    return pts


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2 ** 16 - 1), st.integers(1, 3), st.integers(12, 40))
def test_prefix_tree_roundtrip_properties(seed, n_slots, n_ops):
    """Random insert/match/release/evict workload over a tiny pool drawn
    from a 2-token alphabet (prefix collisions everywhere). Invariants
    after every op: refcount == slot references + tree references (never
    negative), zero refs <=> free-listed, match returns a snapped true
    prefix of an inserted prompt shorter than the query."""
    rnd = np.random.default_rng(seed)
    cfg = _cfg("off")
    ps = 8
    cache = PagedKVCache(cfg, n_slots, max_len=32, page_size=ps, n_pages=20)
    pc = PrefixCache(cache)
    inserted = []          # prompts the tree has seen
    live = {}              # slot -> pages owed to release

    def invariants():
        cache.check_refs(pc.all_pages())
        assert (cache.ref >= 0).all()

    for _ in range(n_ops):
        op = rnd.integers(0, 4)
        if op <= 1:                                   # admit + insert
            free = [s for s in range(n_slots) if s not in live]
            if not free:
                continue
            slot = free[0]
            p_len = int(rnd.integers(4, 25))
            toks = rnd.integers(0, 2, p_len).astype(np.int32)
            hit = pc.match(toks)
            assert hit.reuse_len <= p_len - 1
            if hit.reuse_len:
                # a true prefix of something inserted earlier
                assert any(len(q) >= hit.reuse_len
                           and np.array_equal(q[:hit.reuse_len],
                                              toks[:hit.reuse_len])
                           for q in inserted)
                assert (hit.reuse_len % ps == 0
                        or any(len(q) == hit.reuse_len for q in inserted))
            shared = hit.pages[:-1] if hit.cow_src is not None else hit.pages
            if not cache.allocate(slot, p_len + 2, prefix_pages=shared):
                continue
            invariants()
            n_pg = cache.pages_needed(p_len)
            pc.insert(toks, [int(p) for p in cache.page_table[slot, :n_pg]],
                      _aligned_snaps(p_len, ps))
            inserted.append(toks)
            live[slot] = True
        elif op == 2 and live:                        # release a slot
            slot = list(live)[int(rnd.integers(0, len(live)))]
            cache.release(slot)
            del live[slot]
        elif op == 3:                                 # evict some leaves
            pc.evict_lru(int(rnd.integers(1, 5)))
        invariants()
    # drain: zero live refs => every page reclaimable
    for slot in list(live):
        cache.release(slot)
    pc.evict_lru(cache.n_pages + 1)
    cache.check_refs(pc.all_pages())
    assert pc.all_pages() == []
    assert cache.free_pages == cache.n_pages - 1


def test_refcount_underflow_raises():
    cfg = _cfg("off")
    cache = PagedKVCache(cfg, 1, max_len=16, page_size=8)
    assert cache.allocate(0, 10)
    pages = [int(p) for p in cache.page_table[0] if p]
    cache.release(0)
    with pytest.raises(AssertionError, match="underflow"):
        cache.unref(pages)


def test_match_snaps_to_reuse_points_and_cow():
    """A 20-token prompt (ps=8) caches reuse points at 8, 16 and 20.
    Extending prompts reuse 20 tokens through a COW tail page; an exact
    duplicate must snap down to 16 (at least one token recomputed); a
    prompt diverging mid-page snaps to the last aligned point."""
    cfg = _cfg("off")
    ps = 8
    cache = PagedKVCache(cfg, 1, max_len=32, page_size=ps, n_pages=16)
    pc = PrefixCache(cache)
    rnd = np.random.default_rng(0)
    toks = rnd.integers(0, 50, 20).astype(np.int32)
    assert cache.allocate(0, 24)
    pc.insert(toks, [int(p) for p in cache.page_table[0, :3]],
              _aligned_snaps(20, ps))

    ext = np.concatenate([toks, [7, 8, 9]])
    hit = pc.match(ext)
    assert hit.reuse_len == 20 and len(hit.pages) == 3
    assert hit.cow_src == hit.pages[-1]        # partial tail page: COW
    assert pc.match(toks).reuse_len == 16      # duplicate: snap a page down
    assert pc.match(toks).cow_src is None
    div = toks.copy()
    div[18] += 1                               # diverge mid-tail-page
    assert pc.match(div).reuse_len == 16
    assert pc.match(toks[:9]).reuse_len == 8   # short query caps at P-1


def test_split_invalidates_cut_and_insert_heals():
    """Diverging inside a cached node splits it: the cut point is not an
    exact reuse point (the aggregate mass cannot be decomposed there)
    until a later insertion ending exactly there heals it."""
    cfg = _cfg("off")
    ps = 8
    cache = PagedKVCache(cfg, 2, max_len=32, page_size=ps, n_pages=24)
    pc = PrefixCache(cache)
    rnd = np.random.default_rng(1)
    base = rnd.integers(0, 50, 16).astype(np.int32)
    assert cache.allocate(0, 20)
    # snapshot only at the prompt end: one 16-token node, no interior cut
    pc.insert(base, [int(p) for p in cache.page_table[0, :2]], {16: None})
    fork = base.copy()
    fork[12] += 1                              # splits the node at 12
    assert cache.allocate(1, 20)
    pc.insert(fork, [int(p) for p in cache.page_table[1, :2]], {16: None})
    probe = np.concatenate([base[:12], [99] * 8]).astype(np.int32)
    assert pc.match(probe).reuse_len == 0      # cut at 12 not reusable
    # both originals still fully reusable through the split
    assert pc.match(np.concatenate([base, [1]])).reuse_len == 16
    assert pc.match(np.concatenate([fork, [1]])).reuse_len == 16
    cache.check_refs(pc.all_pages())


# ---------------------------------------------------------------------------
# acceptance: prefix-hit admission == cold admission, token for token
# ---------------------------------------------------------------------------

def _run(cfg, params, prompts, *, prefix, n_slots=2, max_new=8, gap=8,
         **ekw):
    eng = ServeEngine(cfg, params, config=EngineConfig(
        n_slots=n_slots, max_len=64, page_size=8, segment_len=8,
        max_new_cap=max_new, prefill_chunk=8, prefix_cache=prefix, **ekw))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, tokens=p, max_new=max_new,
                           arrival=gap * i))
    outs = eng.run()
    return outs, eng


def _shared_prefix_prompts(cfg, n=3, shared_len=24, tail=8, seed=0):
    rnd = np.random.default_rng(seed)
    shared = rnd.integers(0, cfg.vocab_size, shared_len).astype(np.int32)
    return [np.concatenate([shared, rnd.integers(0, cfg.vocab_size,
                                                 tail).astype(np.int32)])
            for _ in range(n)]


@pytest.mark.parametrize("mode,factor,kernel", [
    ("adaptive", None, False),          # dense paged read, live ranks
    ("fixed", True, False),             # factor-form cache, XLA
    ("fixed", True, True),              # factor-form cache, Pallas kernel
    ("off", None, False),               # no rank path, pages only
])
def test_prefix_hit_parity_with_cold(mode, factor, kernel):
    """Shared-system-prompt traffic: later requests hit the cached prefix
    (arrivals spaced past the first prefill) and must decode exactly the
    tokens the cache-off engine produces — the rehydrated mass row seeds
    the same weighted-Gram first decision a cold prefill would take."""
    cfg = _cfg(mode)
    params = get_model(cfg).init(RNG)
    prompts = _shared_prefix_prompts(cfg, n=3)
    kw = dict(factor_cache=factor, use_kernel=kernel)
    outs_on, eng_on = _run(cfg, params, prompts, prefix=True, **kw)
    outs_off, _ = _run(cfg, params, prompts, prefix=False, **kw)
    for i in range(len(prompts)):
        np.testing.assert_array_equal(
            outs_on[i], outs_off[i],
            err_msg=f"stream {i}: prefix-hit decode diverged from cold")
    s = eng_on.stats
    assert s["prefix_hits"] == 2 and s["prefix_misses"] == 1
    assert s["prefix_reused_tokens"] == 2 * 24
    # ISSUE metric: prefill tokens computed shrink by the reused amount
    assert s["prefill_tokens"] == sum(len(p) for p in prompts) - 2 * 24
    # page accounting: every non-tree page back in the pool, refcounts ==
    # references (the generalized leak invariant)
    eng_on.cache.check_refs(eng_on.prefix.all_pages())
    tree = len(eng_on.prefix.all_pages())
    assert eng_on.cache.free_pages == eng_on.cache.n_pages - 1 - tree


def test_prefix_cow_and_duplicate_parity():
    """Reuse at a prompt-end point (mid-page): the extending request COWs
    the shared tail page; the exact duplicate snaps down to the page
    boundary. Both must match the cache-off engine token for token."""
    cfg = _cfg("adaptive")
    params = get_model(cfg).init(RNG)
    rnd = np.random.default_rng(3)
    p1 = rnd.integers(0, cfg.vocab_size, 20).astype(np.int32)
    prompts = [p1,
               np.concatenate([p1, rnd.integers(0, cfg.vocab_size,
                                                8).astype(np.int32)]),
               p1.copy()]
    outs_on, eng_on = _run(cfg, params, prompts, prefix=True)
    outs_off, _ = _run(cfg, params, prompts, prefix=False)
    for i in range(3):
        np.testing.assert_array_equal(outs_on[i], outs_off[i])
    s = eng_on.stats
    assert s["prefix_cow"] == 1                 # the extension COWed
    assert s["prefix_reused_tokens"] == 20 + 16  # end point + snapped dup
    eng_on.cache.check_refs(eng_on.prefix.all_pages())


def test_prefix_hit_on_recycled_slot():
    """More requests than slots: a hit rides a slot whose previous
    occupant left stale mass/kt/prompt state behind."""
    cfg = _cfg("adaptive")
    params = get_model(cfg).init(RNG)
    prompts = _shared_prefix_prompts(cfg, n=3, seed=4)
    kw = dict(n_slots=1, factor_cache=True)
    outs_on, eng_on = _run(cfg, params, prompts, prefix=True, **kw)
    outs_off, _ = _run(cfg, params, prompts, prefix=False, **kw)
    for i in range(3):
        np.testing.assert_array_equal(outs_on[i], outs_off[i])
    assert eng_on.stats["prefix_hits"] >= 1


def test_prefix_parity_under_eviction_pressure():
    """A pool with zero prefix headroom forces LRU eviction while serving
    two alternating prefix families through one slot; hits that survive
    must stay token-exact and the refcount invariant must hold through
    evict/release interleavings."""
    cfg = _cfg("adaptive")
    params = get_model(cfg).init(RNG)
    rnd = np.random.default_rng(5)
    fam_a = rnd.integers(0, cfg.vocab_size, 16).astype(np.int32)
    fam_b = rnd.integers(0, cfg.vocab_size, 16).astype(np.int32)
    prompts = [np.concatenate([base, rnd.integers(0, cfg.vocab_size,
                                                  6).astype(np.int32)])
               for base in (fam_a, fam_a, fam_b, fam_b, fam_a)]

    def run(prefix):
        eng = ServeEngine(cfg, params, config=EngineConfig(
            n_slots=1, max_len=32, page_size=8, segment_len=8, max_new_cap=4,
            prefill_chunk=8, prefix_cache=prefix,
            prefix_pages=0 if prefix else None))
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, tokens=p, max_new=4, arrival=0))
        return eng.run(), eng

    outs_on, eng_on = run(True)
    outs_off, _ = run(False)
    for i in range(len(prompts)):
        np.testing.assert_array_equal(outs_on[i], outs_off[i])
    assert eng_on.stats["prefix_evictions"] > 0
    assert eng_on.stats["prefix_hits"] >= 1
    eng_on.cache.check_refs(eng_on.prefix.all_pages())


def test_prefix_sampled_stream_parity():
    """Sampling PRNG folds (seed, output index): a sampled stream draws
    identically whether its prompt came from a prefix hit or a cold
    prefill."""
    cfg = _cfg("adaptive")
    params = get_model(cfg).init(RNG)
    prompts = _shared_prefix_prompts(cfg, n=2, seed=6)

    def run(prefix):
        eng = ServeEngine(cfg, params, config=EngineConfig(
            n_slots=2, max_len=64, page_size=8, segment_len=8, max_new_cap=8,
            prefill_chunk=8, prefix_cache=prefix, sampling=True))
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, tokens=p, max_new=8, arrival=8 * i,
                               temperature=0.7, top_k=12, seed=11 + i))
        return eng.run()

    on, off = run(True), run(False)
    for i in range(2):
        np.testing.assert_array_equal(on[i], off[i])


def test_engine_reset_clears_tree_and_validation():
    cfg = _cfg("adaptive")
    params = get_model(cfg).init(RNG)
    with pytest.raises(ValueError, match="prefill_chunk"):
        EngineConfig(prefill_chunk=0, prefix_cache=True)
    eng = Engine(cfg, params, config=EngineConfig(
        n_slots=2, max_len=64, page_size=8, prefill_chunk=8,
        max_new_cap=8, prefix_cache=True))
    prompts = _shared_prefix_prompts(cfg, n=2, seed=7)
    for p in prompts:
        eng.submit(p, SamplingParams(max_new=4))
    eng.run()
    assert eng.core.prefix.n_nodes > 0
    eng.reset()
    assert eng.core.prefix.n_nodes == 0
    assert eng.core.prefix.all_pages() == []
    assert eng.stats["prefix_hits"] == 0
    assert eng.core.cache.free_pages == eng.core.cache.n_pages - 1


# ---------------------------------------------------------------------------
# satellite: thread-safe submit
# ---------------------------------------------------------------------------

def test_submit_from_background_thread():
    """Requests submitted from a non-loop thread while the step loop runs
    must all complete with the same tokens an upfront submission yields
    (per-stream decode is batching/admission-invariant)."""
    cfg = _cfg("off")
    params = get_model(cfg).init(RNG)
    rnd = np.random.default_rng(8)
    prompts = [rnd.integers(0, cfg.vocab_size, s).astype(np.int32)
               for s in (9, 13, 11, 7)]

    ref_eng = Engine(cfg, params, config=EngineConfig(
        n_slots=2, max_len=64, page_size=8, prefill_chunk=8, max_new_cap=6,
        sampling=False))
    ref_handles = [ref_eng.submit(p, SamplingParams(max_new=6))
                   for p in prompts]
    ref_eng.run()

    eng = Engine(cfg, params, config=EngineConfig(
        n_slots=2, max_len=64, page_size=8, prefill_chunk=8, max_new_cap=6,
        sampling=False))
    first = eng.submit(prompts[0], SamplingParams(max_new=6))
    rest = []

    def feeder():
        for p in prompts[1:]:
            rest.append(eng.submit(p, SamplingParams(max_new=6)))

    t = threading.Thread(target=feeder)
    t.start()
    # drive the loop until the feeder finished AND everything drained
    # (check liveness BEFORE stepping: a submit landing after a False
    # step() is then seen by the next iteration, never dropped)
    while True:
        alive = t.is_alive()
        more = eng.step()
        if not alive and not more:
            break
    t.join()
    handles = [first] + rest
    assert all(h.done for h in handles)
    for h, r in zip(handles, ref_handles):
        np.testing.assert_array_equal(h.result(), r.result())
    assert eng.core.cache.free_pages == eng.core.cache.n_pages - 1
