"""Chunked prefill interleaved into the fused decode step.

Covers the serve.api tentpole's data-plane half:
  * token-for-token parity: staggered heterogeneous streams split into
    narrow chunks decode identically to a covering chunk (one chunk at
    least as long as the longest prompt: the whole prompt in one mixed
    step) — dense + factor cache, kernel + XLA paths, remainder chunks
    included,
  * the chunk-accumulated attention-mass seed equals the prompt's causal
    softmax mass computed here from the plain forward's q/k, at every
    chunk width,
  * admission/eviction safety for prompts still in flight: a mid-prefill
    slot is never double-admitted, never evicted early (stale EOS /
    max_new cannot fire before token 0 exists), and the page-leak
    invariant holds through an immediate post-prefill EOS eviction.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import RankConfig
from repro.models import transformer as tr
from repro.models.api import get_model
from repro.serve import EngineConfig, Request, ServeEngine


pytestmark = pytest.mark.serve

RNG = jax.random.PRNGKey(0)


def _cfg(mode="adaptive", **kw):
    cfg = get_config("drrl-paper", reduced=True)
    return cfg.with_(rank=RankConfig(mode=mode, rank_grid=(4, 8, 12, 16),
                                     segment_len=8, **kw))


def _run(cfg, params, prompts, *, chunk, n_slots=3, max_new=12,
         max_len=64, arrivals=None, eos=None, **ekw):
    eng = ServeEngine(cfg, params, config=EngineConfig(
        n_slots=n_slots, max_len=max_len, page_size=8, segment_len=8,
        max_new_cap=max_new, prefill_chunk=chunk, **ekw))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, tokens=p, max_new=max_new,
                           arrival=(arrivals[i] if arrivals else 2 * i),
                           eos_id=eos))
    outs = eng.run()
    return outs, eng


# ---------------------------------------------------------------------------
# token parity: narrow chunks == a covering chunk on the staggered workload
# ---------------------------------------------------------------------------

def _cover(prompts):
    """The covering chunk width: the longest prompt fits one chunk."""
    return max(len(p) for p in prompts)


def test_chunked_parity_staggered_streams():
    """4 mixed-length staggered requests through 3 slots (one recycled),
    remainder chunks included (13, 20, 9, 15 with C=5): tokens must match
    the covering chunk exactly while two rank buckets are live, and
    admission must interleave (mixed steps > 0)."""
    cfg = _cfg("adaptive")
    params = get_model(cfg).init(RNG)
    rnd = np.random.default_rng(0)
    prompts = [np.full((13,), 7, np.int32)] + [
        rnd.integers(0, cfg.vocab_size, s).astype(np.int32)
        for s in (20, 9, 15)]
    outs_1, eng_1 = _run(cfg, params, prompts, chunk=_cover(prompts))
    outs_c, eng_c = _run(cfg, params, prompts, chunk=5)
    for i in range(len(prompts)):
        np.testing.assert_array_equal(
            outs_c[i], outs_1[i],
            err_msg=f"stream {i}: chunk 5 diverged from the covering chunk")
    assert eng_c.stats["mixed_steps"] > 0
    # heterogeneous ranks in one fused step
    distinct = max(len({r for r in step.tolist() if r >= 0})
                   for step in eng_c.ranks_per_step())
    assert distinct >= 2
    # page-leak invariant after the full run
    for eng in (eng_1, eng_c):
        assert eng.cache.free_pages == eng.cache.n_pages - 1
        assert (eng.cache.page_table == 0).all()


@pytest.mark.parametrize("use_kernel,factor", [(True, None), (False, True),
                                               (True, True)])
def test_chunked_parity_kernel_and_factor(use_kernel, factor):
    """The mixed step's per-row q_len path through the Pallas kernel and
    the factor-form cache must keep narrow == covering chunk token
    parity."""
    cfg = _cfg("fixed", fixed_rank=16)
    params = get_model(cfg).init(RNG)
    rnd = np.random.default_rng(1)
    prompts = [rnd.integers(0, cfg.vocab_size, s).astype(np.int32)
               for s in (13, 21)]
    kw = dict(n_slots=2, max_new=8, use_kernel=use_kernel,
              factor_cache=factor)
    outs_1, _ = _run(cfg, params, prompts, chunk=_cover(prompts), **kw)
    outs_c, _ = _run(cfg, params, prompts, chunk=8, **kw)
    for i in range(len(prompts)):
        np.testing.assert_array_equal(outs_c[i], outs_1[i])


def test_chunked_parity_rank_off():
    cfg = _cfg("off")
    params = get_model(cfg).init(RNG)
    rnd = np.random.default_rng(2)
    prompts = [rnd.integers(0, cfg.vocab_size, s).astype(np.int32)
               for s in (11, 17)]
    outs_1, _ = _run(cfg, params, prompts, chunk=_cover(prompts), n_slots=2,
                     max_new=8)
    outs_c, _ = _run(cfg, params, prompts, chunk=4, n_slots=2, max_new=8)
    for i in range(len(prompts)):
        np.testing.assert_array_equal(outs_c[i], outs_1[i])


# ---------------------------------------------------------------------------
# chunk-aware attention-mass seeding
# ---------------------------------------------------------------------------

def _seed_mass(cfg, params, prompt, chunk):
    """Mass-pool contents of slot 0's pages at the exact prefill boundary:
    right after the finishing mixed step, before any decode step adds its
    own row. (L, s, hkv)."""
    eng = ServeEngine(cfg, params, config=EngineConfig(
        n_slots=1, max_len=32, page_size=8, segment_len=64, max_new_cap=4,
        prefill_chunk=chunk))
    eng.submit(Request(rid=0, tokens=prompt, max_new=4))
    st = eng.sched.slots[0]
    while not st.active or st.mid_prefill:
        eng.step()
    return np.asarray(eng.cache.mass_pool)[:, 0, :len(prompt)]


def _plain_mass(cfg, params, prompt):
    """The prompt's per-key attention mass from the plain full-rank
    forward: the post-RoPE q/k it captures, a causal softmax per query
    head, summed over queries, then the mean over each kv head's query
    heads. (L, s, hkv)."""
    off = cfg.with_(rank=RankConfig(mode="off"))
    _, aux = tr.forward_dense(off, params, prompt[None], collect_aux="rl",
                              collect_qkv=True)
    q = np.asarray(aux["layers"]["qkv"]["q"], np.float64)[:, 0]
    k = np.asarray(aux["layers"]["qkv"]["k"], np.float64)[:, 0]
    L, s, hq, dh = q.shape
    hkv = k.shape[2]
    kr = np.repeat(k, hq // hkv, axis=2)              # q head g*n_rep+n -> g
    sc = np.einsum("lqhd,lkhd->lhqk", q, kr) * dh ** -0.5
    sc = np.where(np.tril(np.ones((s, s), bool)), sc, -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    mass = p.sum(2).reshape(L, hkv, hq // hkv, s).mean(2)   # (L, hkv, s)
    return np.swapaxes(mass, 1, 2)


def test_chunked_mass_seed_matches_oneshot():
    """The weighted-Gram basis must see the full prompt mass: at every
    chunk width — split chunks, a chunk that ends exactly on the prompt,
    and one wider than it — the accumulated seed equals the prompt's
    causal softmax mass from the plain forward, to f32 rounding."""
    cfg = _cfg("adaptive")
    params = get_model(cfg).init(RNG)
    prompt = np.random.default_rng(3).integers(
        0, cfg.vocab_size, 13).astype(np.int32)
    ref = _plain_mass(cfg, params, prompt)
    assert np.abs(ref).max() > 0.0
    for C in (4, 5, 13, 32):
        np.testing.assert_allclose(_seed_mass(cfg, params, prompt, C), ref,
                                   rtol=0.0, atol=1e-6,
                                   err_msg=f"chunk {C}")


# ---------------------------------------------------------------------------
# mid-prefill admission/eviction safety
# ---------------------------------------------------------------------------

def test_mid_prefill_never_evicted_or_double_admitted():
    from repro.serve import PagedKVCache, Scheduler
    cfg = _cfg("off")
    cache = PagedKVCache(cfg, 1, max_len=32, page_size=8)
    sched = Scheduler(1)
    sched.submit(Request(rid=0, tokens=np.arange(16), max_new=1, eos_id=5))
    [(slot, req)] = sched.admit(0, cache.allocate)
    st = sched.slots[slot]
    st.prefilled = 8                      # chunked prompt half consumed
    assert st.mid_prefill
    # stale state from a previous occupant must not evict the new stream:
    # n_out >= max_new and last_tok == eos are both meaningless pre-token-0
    st.n_out, st.last_tok = 1, 5
    assert not sched.should_evict(slot)
    # the busy slot is not offered to the next request
    sched.submit(Request(rid=1, tokens=np.arange(4), max_new=1))
    assert sched.admit(1, cache.allocate) == []
    # once the prompt is fully consumed, the normal rules apply again
    st.prefilled = st.prompt_len
    assert sched.should_evict(slot)


def test_page_leak_mid_prefill_eos_eviction():
    """EOS as the very first generated token right after a chunked
    prefill: the slot must evict cleanly and return every page."""
    cfg = _cfg("off")
    params = get_model(cfg).init(RNG)
    prompt = np.arange(10, dtype=np.int32)
    outs, _ = _run(cfg, params, [prompt], chunk=4, n_slots=1, max_new=6,
                   arrivals=[0])
    eos = int(outs[0][0])                 # token 0 of the unconstrained run
    outs2, eng2 = _run(cfg, params, [prompt], chunk=4, n_slots=1, max_new=6,
                       arrivals=[0], eos=eos)
    assert outs2[0].tolist() == [eos]     # stopped immediately after prefill
    assert eng2.cache.free_pages == eng2.cache.n_pages - 1
    assert (eng2.cache.page_table == 0).all()


def test_chunked_recycled_slot_isolation():
    """A stream riding a recycled slot under chunked admission decodes as
    if it had the engine to itself (stale kt/mass/prompt_buf state from
    the previous occupant must not leak through the mixed step)."""
    cfg = _cfg("adaptive")
    params = get_model(cfg).init(RNG)
    rnd = np.random.default_rng(4)
    p1 = rnd.integers(0, cfg.vocab_size, 14).astype(np.int32)
    p2 = rnd.integers(0, cfg.vocab_size, 11).astype(np.int32)
    outs, eng = _run(cfg, params, [p1, p2], chunk=4, n_slots=1, max_new=8,
                     arrivals=[0, 0], factor_cache=True)
    solo, _ = _run(cfg, params, [p2], chunk=4, n_slots=1, max_new=8,
                   arrivals=[0], factor_cache=True)
    np.testing.assert_array_equal(outs[1], solo[0])
    assert eng.cache.free_pages == eng.cache.n_pages - 1


# ---------------------------------------------------------------------------
# sampling under chunked admission
# ---------------------------------------------------------------------------

def test_sampled_stream_parity_chunked_vs_oneshot():
    """The sampling PRNG folds (seed, output index), so a sampled stream's
    draws are independent of the chunk width: a narrow chunk and the
    covering chunk must produce identical sampled tokens."""
    cfg = _cfg("adaptive")
    params = get_model(cfg).init(RNG)
    rnd = np.random.default_rng(5)
    prompts = [rnd.integers(0, cfg.vocab_size, s).astype(np.int32)
               for s in (13, 9)]

    def run(chunk):
        eng = ServeEngine(cfg, params, config=EngineConfig(
            n_slots=2, max_len=64, page_size=8, segment_len=8,
            max_new_cap=8, prefill_chunk=chunk))
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, tokens=p, max_new=8, arrival=2 * i,
                               temperature=0.7, top_k=12, seed=41 + i))
        return eng.run()

    outs_1, outs_c = run(_cover(prompts)), run(6)
    for i in range(len(prompts)):
        np.testing.assert_array_equal(outs_c[i], outs_1[i])
