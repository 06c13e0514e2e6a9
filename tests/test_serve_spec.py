"""Low-rank self-speculative decoding (repro.serve.spec).

The acceptance property is exactness: speculation may only change speed,
never tokens. Covers:
  * token parity with plain decode — greedy and seeded top-k / top-p
    streams, across dense/factor caches, kernel/XLA lowering, and
    off/fixed/adaptive rank modes,
  * the sampling PRNG folding on (seed, absolute output position): draw
    streams are bitwise identical with speculation on/off and across
    accept/reject histories,
  * rollback page accounting: no leaked or rewound pages under
    refcounting with live prefix-cache hits (speculative writes never
    touch a shared page),
  * mid-stream cancellation while drafts are in flight stays leak-free,
  * per-request accept-length stats (sum == generated tokens, values in
    [1, draft_k + 1]),
  * snapshot-density throttling (EngineConfig.snapshot_every): sparser
    reuse points, parity preserved via nearest-earlier-snapshot fallback,
  * pure helper units (accept_counts / clamp_to_eos) and EngineConfig
    validation.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import RankConfig
from repro.models.api import get_model
from repro.serve import Request, ServeEngine
from repro.serve import spec as spec_mod
from repro.serve.api import Engine, EngineConfig, SamplingParams


pytestmark = pytest.mark.serve

import jax

RNG = jax.random.PRNGKey(0)


def _cfg(mode="adaptive", **kw):
    cfg = get_config("drrl-paper", reduced=True)
    return cfg.with_(rank=RankConfig(mode=mode, rank_grid=(4, 8, 12, 16),
                                     fixed_rank=16, segment_len=8, **kw))


def _prompts(cfg, sizes=(9, 17, 12), seed=0):
    rnd = np.random.default_rng(seed)
    return [rnd.integers(1, cfg.vocab_size, s).astype(np.int32)
            for s in sizes]


def _run(cfg, params, prompts, *, speculative, max_new=12, reqs=None,
         **ekw):
    eng = ServeEngine(cfg, params, config=EngineConfig(
        n_slots=4, max_len=64, page_size=8, segment_len=8, max_new_cap=32,
        prefill_chunk=8, speculative=speculative, draft_k=3,
        draft_rank_frac=0.5, **ekw))
    for i, p in enumerate(prompts):
        kw = dict(reqs[i]) if reqs else {}
        eng.submit(Request(rid=i, tokens=p, max_new=max_new, **kw))
    outs = eng.run()
    return outs, eng


# ---------------------------------------------------------------------------
# pure helper units
# ---------------------------------------------------------------------------

def test_accept_counts_longest_prefix():
    drafts = jnp.array([[5, 6, 7],      # all match
                        [5, 9, 7],      # mismatch at i=1
                        [9, 6, 7],      # immediate mismatch
                        [5, 6, 9]])     # mismatch at last
    targets = jnp.array([[5, 6, 7, 8],
                         [5, 6, 7, 8],
                         [5, 6, 7, 8],
                         [5, 6, 7, 8]])
    np.testing.assert_array_equal(
        np.asarray(spec_mod.accept_counts(drafts, targets)), [4, 2, 1, 3])


def test_clamp_to_eos():
    a = jnp.array([4, 4, 4, 4], jnp.int32)
    targets = jnp.array([[5, 6, 7, 8],      # no EOS
                         [5, 2, 7, 8],      # EOS at 1 -> emit through it
                         [2, 6, 7, 8],      # EOS first -> a == 1
                         [5, 2, 7, 8]])     # eos_id == -1 -> no clamp
    eos = jnp.array([2, 2, 2, -1], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(spec_mod.clamp_to_eos(a, targets, eos)), [4, 2, 1, 4])


def test_apply_deferred_mass_matches_sequential():
    """Ordered masked application == sequential per-token accumulation,
    bitwise, for every accept count."""
    rnd = np.random.default_rng(0)
    L, ns, C, M, hkv = 2, 3, 4, 16, 2
    pool = jnp.asarray(rnd.random((L, ns, M, hkv), np.float32))
    contrib = jnp.asarray(rnd.random((L, ns, C, M, hkv), np.float32))
    lens = jnp.array([3, 7, 0], jnp.int32)
    n_q = jnp.array([2, 4, 0], jnp.int32)
    got = spec_mod.apply_deferred_mass(pool, contrib, lens, n_q)
    want = np.asarray(pool).copy()
    for r, (l0, nq) in enumerate(zip([3, 7, 0], [2, 4, 0])):
        want[:, r, l0:l0 + nq] = 0.0
        for q in range(nq):
            want[:, r] = want[:, r] + np.asarray(contrib)[:, r, q]
    np.testing.assert_array_equal(np.asarray(got), want)


# ---------------------------------------------------------------------------
# acceptance: token parity with plain decode, all modes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode,factor,kernel", [
    ("adaptive", None, False),          # factor cache, live ranks, XLA
    ("adaptive", None, True),           # factor cache, Pallas kernel
    ("fixed", True, False),             # factor cache, fixed rank
    ("fixed", False, False),            # dense paged read at fixed rank
    ("off", None, False),               # no rank path at all
])
def test_spec_parity_greedy(mode, factor, kernel):
    cfg = _cfg(mode)
    params = get_model(cfg).init(RNG)
    prompts = _prompts(cfg)
    kw = dict(factor_cache=factor, use_kernel=kernel)
    off, _ = _run(cfg, params, prompts, speculative=False, **kw)
    on, eng = _run(cfg, params, prompts, speculative=True, **kw)
    for i in range(len(prompts)):
        np.testing.assert_array_equal(
            on[i], off[i],
            err_msg=f"stream {i}: speculative decode diverged")
    s = eng.stats
    assert s["spec_steps"] > 0
    # every decoding row-step emits its verify bonus token plus accepts;
    # each engine step covers >= 1 decoding row
    assert s["spec_tokens"] - s["spec_accepted"] >= s["spec_steps"]
    # page accounting unchanged by rollback
    assert eng.cache.free_pages == eng.cache.n_pages - 1
    assert (eng.cache.page_table == 0).all()


def test_spec_parity_sampled_streams():
    """Seeded top-k and top-p streams are bitwise identical with
    speculation on/off: targets reuse the same (seed, output position)
    fold plain decode samples with."""
    cfg = _cfg("adaptive")
    params = get_model(cfg).init(RNG)
    prompts = _prompts(cfg, seed=3)
    reqs = [dict(temperature=0.9, top_k=8, seed=11),
            dict(temperature=0.7, top_p=0.85, seed=12),
            dict()]                                    # greedy rides along
    kw = dict(sampling=True, nucleus=True, reqs=reqs)
    off, _ = _run(cfg, params, prompts, speculative=False, **kw)
    on, eng = _run(cfg, params, prompts, speculative=True, **kw)
    for i in range(len(prompts)):
        np.testing.assert_array_equal(
            on[i], off[i], err_msg=f"sampled stream {i} diverged")
    assert eng.stats["spec_accepted"] > 0


def test_spec_parity_with_eos_cutoff():
    """A draft run crossing EOS truncates at it — same stop token, same
    stream length as plain decode."""
    cfg = _cfg("off")
    params = get_model(cfg).init(RNG)
    prompts = _prompts(cfg, sizes=(9, 13), seed=5)
    # pick each stream's own 3rd greedy token as its EOS so the cutoff
    # genuinely lands mid-run
    probe, _ = _run(cfg, params, prompts, speculative=False, max_new=6)
    reqs = [dict(eos_id=int(probe[i][2])) for i in range(len(prompts))]
    off, _ = _run(cfg, params, prompts, speculative=False, reqs=reqs)
    on, eng = _run(cfg, params, prompts, speculative=True, reqs=reqs)
    for i in range(len(prompts)):
        np.testing.assert_array_equal(on[i], off[i])
        assert on[i][-1] == reqs[i]["eos_id"]
    assert eng.cache.free_pages == eng.cache.n_pages - 1


# ---------------------------------------------------------------------------
# rollback + prefix cache: shared pages are never touched, nothing leaks
# ---------------------------------------------------------------------------

def test_spec_rollback_with_live_prefix_hits():
    """Speculative decode over prefix-hit admissions: rejected drafts
    roll back without touching refcounted shared pages, outputs match the
    cold cache-off engine, and the generalized leak invariant holds."""
    cfg = _cfg("adaptive")
    params = get_model(cfg).init(RNG)
    rnd = np.random.default_rng(6)
    shared = rnd.integers(0, cfg.vocab_size, 24).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rnd.integers(0, cfg.vocab_size,
                                            8).astype(np.int32)])
               for _ in range(3)]
    reqs = [dict(arrival=10 * i) for i in range(3)]
    off, _ = _run(cfg, params, prompts, speculative=False, reqs=reqs)
    on, eng = _run(cfg, params, prompts, speculative=True,
                   prefix_cache=True, reqs=reqs)
    for i in range(3):
        np.testing.assert_array_equal(
            on[i], off[i], err_msg=f"prefix-hit stream {i} diverged")
    assert eng.stats["prefix_hits"] == 2
    eng.cache.check_refs(eng.prefix.all_pages())
    tree = len(eng.prefix.all_pages())
    assert eng.cache.free_pages == eng.cache.n_pages - 1 - tree


def test_spec_prefix_inserter_decodes_into_its_shared_tail_page():
    """Prompts that end mid-page: once a prompt is in the prefix tree,
    its own slot keeps decoding (speculatively) into the tail page the
    tree now also holds — the tree claims only the prompt's positions, so
    that is legal, and hits on it still match cold decode."""
    cfg = _cfg("adaptive")
    params = get_model(cfg).init(RNG)
    rnd = np.random.default_rng(8)
    shared = rnd.integers(0, cfg.vocab_size, 20).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rnd.integers(0, cfg.vocab_size,
                                            5).astype(np.int32)])
               for _ in range(3)]
    reqs = [dict(arrival=10 * i) for i in range(3)]
    off, _ = _run(cfg, params, prompts, speculative=False, reqs=reqs)
    on, eng = _run(cfg, params, prompts, speculative=True,
                   prefix_cache=True, reqs=reqs)
    for i in range(3):
        np.testing.assert_array_equal(
            on[i], off[i], err_msg=f"prefix-hit stream {i} diverged")
    assert eng.stats["prefix_hits"] == 2
    eng.cache.check_refs(eng.prefix.all_pages())


def test_spec_cancel_mid_stream_leak_free():
    """Cancelling a stream between speculative steps releases its pages
    and stops delivery; the survivors finish with correct tokens."""
    cfg = _cfg("adaptive")
    params = get_model(cfg).init(RNG)
    prompts = _prompts(cfg, sizes=(9, 17), seed=7)
    ref = Engine(cfg, params, config=EngineConfig(
        n_slots=2, max_len=64, page_size=8, segment_len=8,
        prefill_chunk=8, max_new_cap=32))
    hr = [ref.submit(p, SamplingParams(max_new=12)) for p in prompts]
    ref.run()

    eng = Engine(cfg, params, config=EngineConfig(
        n_slots=2, max_len=64, page_size=8, segment_len=8,
        prefill_chunk=8, max_new_cap=32, speculative=True, draft_k=3,
        draft_rank_frac=0.5))
    h = [eng.submit(p, SamplingParams(max_new=12)) for p in prompts]
    for _ in range(4):                     # past prefill, drafts in flight
        eng.step()
    assert h[0].cancel()
    eng.run()
    assert h[0].cancelled and not h[1].cancelled
    np.testing.assert_array_equal(h[1].result(), hr[1].result())
    assert eng.core.cache.free_pages == eng.core.cache.n_pages - 1
    assert (eng.core.cache.page_table == 0).all()
    # the cancelled stream's accept history was still harvested
    assert 0 in eng.accept_lens()


# ---------------------------------------------------------------------------
# accept-length stats
# ---------------------------------------------------------------------------

def test_accept_len_stats_account_for_every_token():
    cfg = _cfg("adaptive")
    params = get_model(cfg).init(RNG)
    prompts = _prompts(cfg, seed=9)
    eng = Engine(cfg, params, config=EngineConfig(
        n_slots=4, max_len=64, page_size=8, segment_len=8,
        prefill_chunk=8, max_new_cap=32, speculative=True, draft_k=3,
        draft_rank_frac=0.5))
    hs = [eng.submit(p, SamplingParams(max_new=12)) for p in prompts]
    eng.run()
    acc = eng.accept_lens()
    assert set(acc) == {h.rid for h in hs}
    for h in hs:
        runs = acc[h.rid]
        assert all(1 <= a <= 4 for a in runs)
        # token 0 comes from prefill; every later token from some run
        assert sum(runs) == len(h.result()) - 1
    s = eng.stats
    assert s["spec_drafted"] >= s["spec_accepted"] >= 0
    assert s["spec_tokens"] == sum(sum(v) for v in acc.values())


# ---------------------------------------------------------------------------
# satellite: sampled streams are accept/reject-history invariant
# ---------------------------------------------------------------------------

def test_prng_stream_invariant_to_draft_k():
    """The fold is (seed, absolute output position): the same request
    draws the same stream under different draft depths (different
    accept/reject histories) and without speculation at all."""
    cfg = _cfg("off")
    params = get_model(cfg).init(RNG)
    prompts = _prompts(cfg, sizes=(9,), seed=10)
    reqs = [dict(temperature=0.8, top_k=16, seed=21)]

    outs = []
    for spec, k in [(False, None), (True, 1), (True, 3), (True, 5)]:
        eng = ServeEngine(cfg, params, config=EngineConfig(
            n_slots=2, max_len=64, page_size=8, segment_len=8, max_new_cap=32,
            prefill_chunk=8, speculative=spec, sampling=True,
            **({"draft_k": k} if k else {})))
        eng.submit(Request(rid=0, tokens=prompts[0], max_new=12, **reqs[0]))
        outs.append(eng.run()[0])
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0], o)


# ---------------------------------------------------------------------------
# satellite: snapshot-density throttling
# ---------------------------------------------------------------------------

def test_snapshot_throttle_sparser_reuse_parity():
    """snapshot_every=2 keeps every other page boundary: a prompt
    diverging between kept snapshots falls back to the nearest earlier
    one (shorter reuse, identical tokens)."""
    cfg = _cfg("adaptive")
    params = get_model(cfg).init(RNG)
    rnd = np.random.default_rng(11)
    shared = rnd.integers(0, cfg.vocab_size, 24).astype(np.int32)
    prompts = [np.concatenate([shared, rnd.integers(
        0, cfg.vocab_size, 8).astype(np.int32)]) for _ in range(2)]
    reqs = [dict(arrival=10 * i) for i in range(2)]
    off, _ = _run(cfg, params, prompts, speculative=False, reqs=reqs)

    dense_eng = ServeEngine(cfg, params, config=EngineConfig(
        n_slots=4, max_len=64, page_size=8, segment_len=8, max_new_cap=32,
        prefill_chunk=8, prefix_cache=True))
    sparse_eng = ServeEngine(cfg, params, config=EngineConfig(
        n_slots=4, max_len=64, page_size=8, segment_len=8, max_new_cap=32,
        prefill_chunk=8, prefix_cache=True, snapshot_every=2))
    for eng in (dense_eng, sparse_eng):
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, tokens=p, max_new=12, **reqs[i]))
    dense_out = dense_eng.run()
    sparse_out = sparse_eng.run()
    for i in range(2):
        np.testing.assert_array_equal(dense_out[i], off[i])
        np.testing.assert_array_equal(sparse_out[i], off[i])
    # both hit, but the sparse tree only offers every other boundary:
    # the shared 24 = 3 pages reuse snaps 24 -> 16 under snapshot_every=2
    assert dense_eng.stats["prefix_hits"] == 1
    assert sparse_eng.stats["prefix_hits"] == 1
    assert (sparse_eng.stats["prefix_reused_tokens"]
            <= dense_eng.stats["prefix_reused_tokens"])
    sparse_eng.cache.check_refs(sparse_eng.prefix.all_pages())


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------

def test_spec_config_validation():
    with pytest.raises(ValueError, match="prefill_chunk"):
        EngineConfig(prefill_chunk=0, speculative=True)
    with pytest.raises(ValueError, match="draft_k"):
        EngineConfig(speculative=True, draft_k=0)
    with pytest.raises(ValueError, match="draft_rank_frac"):
        EngineConfig(draft_rank_frac=0.0)
    with pytest.raises(ValueError, match="snapshot_every"):
        EngineConfig(snapshot_every=0)


# ---------------------------------------------------------------------------
# adaptive draft length (EWMA controller)
# ---------------------------------------------------------------------------

def test_adaptive_draft_parity_and_stats():
    """Whatever the EWMA controller does to the draft window, accepts stay
    exact: adaptive-draft streams are token-identical to plain decode,
    and stats expose the effective draft length."""
    cfg = _cfg("adaptive")
    params = get_model(cfg).init(RNG)
    prompts = _prompts(cfg)
    plain, _ = _run(cfg, params, prompts, speculative=False)
    outs, eng = _run(cfg, params, prompts, speculative=True,
                     adaptive_draft=True)
    for i in range(len(prompts)):
        np.testing.assert_array_equal(outs[i], plain[i])
    assert 0 <= eng.stats["eff_draft_k"] <= eng.config.draft_k


def test_adaptive_draft_collapse_routes_plain_decode():
    """shrink_below > 1 shrinks on every spec step (the EWMA can never
    clear it): eff_k decays 3 -> 1 -> 0 and decode rides the mixed step
    with only probe spec steps left. Parity stays exact — the collapsed
    path is the plain fused step, not an approximation."""
    cfg = _cfg("adaptive")
    params = get_model(cfg).init(RNG)
    prompts = _prompts(cfg)
    plain, _ = _run(cfg, params, prompts, speculative=False, max_new=24)
    outs, eng = _run(cfg, params, prompts, speculative=True, max_new=24,
                     adaptive_draft=True, draft_shrink_below=1.01)
    for i in range(len(prompts)):
        np.testing.assert_array_equal(outs[i], plain[i])
    assert eng.stats["eff_draft_k"] == 0
    # collapsed decode steps are NOT spec dispatches (probes excepted)
    assert eng.stats["spec_steps"] < eng.stats["steps"]


def test_adaptive_draft_recovers_from_collapse():
    """A collapsed window grows back through probe steps: with the grow
    threshold always met, eff_k climbs 0 -> 2 -> 3 on the probe cadence
    and the stream still matches plain decode exactly."""
    cfg = _cfg("adaptive")
    params = get_model(cfg).init(RNG)
    prompts = _prompts(cfg)
    plain, _ = _run(cfg, params, prompts, speculative=False, max_new=24)
    eng = ServeEngine(cfg, params, config=EngineConfig(
        n_slots=4, max_len=64, page_size=8, segment_len=8, max_new_cap=32,
        prefill_chunk=8, speculative=True, draft_k=3, draft_rank_frac=0.5,
        adaptive_draft=True, draft_shrink_below=-1.0, draft_grow_above=-1.0))
    eng._eff_k = 0                      # start collapsed
    eng.stats["eff_draft_k"] = 0
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, tokens=p, max_new=24))
    outs = eng.run()
    for i in range(len(prompts)):
        np.testing.assert_array_equal(outs[i], plain[i])
    assert eng.stats["eff_draft_k"] == eng.config.draft_k


def test_adaptive_draft_requires_speculative():
    cfg = _cfg("off")
    params = get_model(cfg).init(RNG)
    with pytest.raises(ValueError, match="adaptive_draft"):
        ServeEngine(cfg, params, config=EngineConfig(adaptive_draft=True))
    with pytest.raises(ValueError, match="adaptive_draft"):
        EngineConfig(adaptive_draft=True)


# ---------------------------------------------------------------------------
# drift-trigger clock under speculation
# ---------------------------------------------------------------------------

def test_drift_check_once_per_accepted_run_post_accept():
    """The drift check fires once per fused step (= once per accepted
    run, NOT once per token) and always against the post-accept
    position: at call time the host lens mirror has already advanced
    past every token the verify pass accepted (the cache holds prompt +
    all emitted tokens but the newest, whose KV lands next dispatch)."""
    cfg = _cfg("adaptive")
    params = get_model(cfg).init(RNG)
    prompts = _prompts(cfg)
    eng = ServeEngine(cfg, params, config=EngineConfig(
        n_slots=4, max_len=64, page_size=8, segment_len=8, max_new_cap=32,
        prefill_chunk=8, speculative=True, draft_k=3, draft_rank_frac=0.5,
        drift_threshold=1e9))
    calls = []
    orig = eng._check_drift

    def spy(live):
        for i in live:
            st = eng.sched.slots[i]
            assert (eng.cache.lens[i]
                    == st.req.tokens.size + st.n_out - 1), \
                f"slot {i}: drift check saw a pre-accept position"
        calls.append(list(live))
        return orig(live)

    eng._check_drift = spy
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, tokens=p, max_new=12))
    eng.run()
    # one check per spec dispatch with decoding rows...
    assert len(calls) == eng.stats["spec_steps"]
    # ...which is strictly coarser than per-token (accepts ran > 1)
    assert eng.stats["tokens_decoded"] > len(calls)


def test_drift_clock_never_firing_is_bitwise_inert():
    """A drift threshold no residual can reach must leave streams
    bitwise identical to running with the trigger off — on the plain
    path and under speculation alike (the check reads, never writes)."""
    cfg = _cfg("adaptive")
    params = get_model(cfg).init(RNG)
    prompts = _prompts(cfg)
    base, _ = _run(cfg, params, prompts, speculative=False)
    for speculative in (False, True):
        outs, eng = _run(cfg, params, prompts, speculative=speculative,
                         drift_threshold=1e9)
        assert not any(eng.force_decide)
        for i in range(len(prompts)):
            np.testing.assert_array_equal(outs[i], base[i])


def test_drift_trigger_under_speculation_forces_redecision():
    """drift_threshold=0 re-decides on every accepted run; the decide
    count must exceed the pure segment schedule's, the re-decision lands
    at the next step (streams may legally diverge from plain decode —
    the paper's adaptation clock just got finer), and streams stay
    valid."""
    cfg = _cfg("adaptive")
    params = get_model(cfg).init(RNG)
    prompts = _prompts(cfg)
    _, eng_base = _run(cfg, params, prompts, speculative=True)
    outs, eng = _run(cfg, params, prompts, speculative=True,
                     drift_threshold=0.0)
    assert eng.stats["decides"] > eng_base.stats["decides"]
    for i in range(len(prompts)):
        assert outs[i].shape == (12,)
