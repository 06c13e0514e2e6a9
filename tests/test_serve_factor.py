"""Serve-time weighted-Gram basis + factor-form paged K cache.

Covers the fixes of the serve data-plane rework:
  * serve-time half-rank top-1 agreement clears the 0.8 bar with the
    softmax-weighted basis (the plain-Gram basis sat at ~0.75 — the bug
    the prefill-path weighted Gram had already fixed),
  * the factored decode path (kt_pool = K . B_r) is token-for-token
    identical to the dense paged path at full rank,
  * recycled-slot isolation: a new occupant of freed pages never reads the
    previous occupant's stale factors / attention mass,
  * page-leak invariant after run(),
  * prefill bucket clamping, random-mode slot fold-in, and the Eq. 9 veto
    actually measuring the previous-segment -> current transition.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import RankConfig
from repro.models import transformer as tr
from repro.models.api import get_model
from repro.models.lowrank_cache import attention_mass
from repro.serve import EngineConfig, PagedKVCache, Request, ServeEngine
from repro.serve.policy import make_decide_fn


pytestmark = pytest.mark.serve

RNG = jax.random.PRNGKey(0)


def _drrl_cfg(mode="fixed", **kw):
    cfg = get_config("drrl-paper", reduced=True)
    return cfg.with_(rank=RankConfig(mode=mode, rank_grid=(4, 8, 12, 16),
                                     segment_len=8, **kw))


# ---------------------------------------------------------------------------
# serve-time basis quality: weighted Gram clears the bar the plain one missed
# ---------------------------------------------------------------------------

def test_serve_halfrank_agreement_weighted_basis():
    """Teacher-forced decode against the paged cache at half rank: the
    decide-time weighted basis must reach >= 0.8 top-1 agreement with the
    full-rank reference AND beat the plain-Gram basis (zero mass falls
    back to plain — the pre-fix serve behaviour, ~0.75 here)."""
    cfg0 = get_config("qwen2.5-14b", reduced=True)
    dh = cfg0.resolved_head_dim()
    half = dh // 2
    cfg = cfg0.with_(rank=RankConfig(mode="fixed", rank_grid=(half, dh),
                                     fixed_rank=half, segment_len=32))
    params = tr.init_dense(cfg0, RNG)
    fns = get_model(cfg)
    pf_cfg = cfg.with_(rank=cfg.rank.__class__(mode="off"))
    fns_off = get_model(pf_cfg)
    b, s, n = 2, 24, 6
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                              cfg.vocab_size)
    nxt = jax.random.randint(jax.random.PRNGKey(2), (b, n), 0,
                             cfg.vocab_size)

    cf = fns_off.init_cache(b, 40)
    _, cf = fns_off.decode_step(params, cf, toks)
    refs = []
    for t in range(n):
        lg, cf = fns_off.decode_step(params, cf, nxt[:, t:t + 1])
        refs.append(np.asarray(lg[:, 0]))
    ref = np.stack(refs, 1)                              # (b, n, V)

    _, aux = tr.forward_dense(pf_cfg, params, toks, collect_aux="rl",
                              collect_qkv=True)
    qkv = aux["layers"]["qkv"]
    mass = attention_mass(qkv["q"], qkv["k"])            # (L, b, hkv, s)

    def run(weighted):
        cache = PagedKVCache(cfg, n_slots=b, max_len=40, page_size=8,
                             factored=True)
        decide = make_decide_fn(cfg)
        for slot in range(b):
            cache.allocate(slot, s + n)
            m = jnp.swapaxes(mass[:, slot], 1, 2) if weighted else None
            cache.write_prefill(slot, qkv["k"][:, slot], qkv["v"][:, slot],
                                mass_layers=m)
            (cache.ranks, cache.basis, cache.spectra, cache.kt_pool,
             _veto) = decide(
                cache.k_pool, cache.mass_pool, cache.kt_pool,
                jnp.asarray(cache.page_table),
                jnp.asarray(cache.lens, jnp.int32), cache.ranks,
                cache.basis, cache.spectra, np.int32(slot),
                np.bool_(False), np.int32(0))
        lens = jnp.asarray(cache.lens, jnp.int32)
        pt = jnp.asarray(cache.page_table)
        outs = []
        for t in range(n):
            logits, pools = fns.decode_step_paged(
                params, cache.k_pool, cache.v_pool, pt, nxt[:, t:t + 1],
                slot_lens=lens, slot_ranks=cache.ranks, basis=cache.basis,
                kt_pool=cache.kt_pool, mass_pool=cache.mass_pool)
            cache.k_pool, cache.v_pool = pools["k"], pools["v"]
            cache.kt_pool, cache.mass_pool = pools["kt"], pools["mass"]
            lens = lens + 1
            outs.append(np.asarray(logits[:, 0]))
        got = np.stack(outs, 1)
        return float(np.mean(np.argmax(got, -1) == np.argmax(ref, -1)))

    agree_plain = run(weighted=False)
    agree_weighted = run(weighted=True)
    assert agree_weighted >= 0.8, (agree_weighted, agree_plain)
    assert agree_weighted > agree_plain, (agree_weighted, agree_plain)


# ---------------------------------------------------------------------------
# factor path == dense paged path at full rank; no page leaks
# ---------------------------------------------------------------------------

def _run_engine(cfg, params, prompts, *, factor, n_slots=2, max_new=12,
                use_kernel=False):
    eng = ServeEngine(cfg, params, config=EngineConfig(
        n_slots=n_slots, max_len=64, page_size=8, segment_len=8,
        max_new_cap=max_new, factor_cache=factor, use_kernel=use_kernel))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, tokens=p, max_new=max_new, arrival=2 * i))
    eng.run()
    return eng


def test_factor_parity_and_page_leak():
    cfg = _drrl_cfg("fixed", fixed_rank=16)        # top of grid == dh: full
    fns = get_model(cfg)
    params = fns.init(RNG)
    rnd = np.random.default_rng(0)
    prompts = [rnd.integers(0, cfg.vocab_size, s).astype(np.int32)
               for s in (12, 20, 9)]
    eng_f = _run_engine(cfg, params, prompts, factor=True)
    eng_d = _run_engine(cfg, params, prompts, factor=False)
    assert eng_f.cache.kt_pool is not None and eng_d.cache.kt_pool is None
    outs_f, outs_d = eng_f.results(), eng_d.results()
    for i in range(len(prompts)):
        np.testing.assert_array_equal(
            outs_f[i], outs_d[i],
            err_msg=f"stream {i}: factored decode diverged at full rank")
    # page-leak invariant: every page back in the pool, tables on scratch
    for eng in (eng_f, eng_d):
        assert eng.cache.free_pages == eng.cache.n_pages - 1
        assert (eng.cache.page_table == 0).all()


def test_factor_parity_kernel_path():
    """The per-row flash-decode kernel consumes the same paged factors (and
    emits the mass row itself): tokens must match the XLA factor path."""
    cfg = _drrl_cfg("fixed", fixed_rank=16)
    fns = get_model(cfg)
    params = fns.init(RNG)
    rnd = np.random.default_rng(1)
    prompts = [rnd.integers(0, cfg.vocab_size, s).astype(np.int32)
               for s in (10, 17)]
    outs_x = _run_engine(cfg, params, prompts, factor=True,
                         max_new=6).results()
    outs_k = _run_engine(cfg, params, prompts, factor=True, max_new=6,
                         use_kernel=True).results()
    for i in range(len(prompts)):
        np.testing.assert_array_equal(outs_k[i], outs_x[i])


def test_recycled_slot_isolation():
    """A stream admitted into a recycled slot (same pages, same factor /
    mass cells) must decode exactly as if it had the engine to itself."""
    cfg = _drrl_cfg("adaptive", energy_threshold=0.90)
    fns = get_model(cfg)
    params = fns.init(RNG)
    rnd = np.random.default_rng(2)
    p1 = rnd.integers(0, cfg.vocab_size, 14).astype(np.int32)
    p2 = rnd.integers(0, cfg.vocab_size, 11).astype(np.int32)
    eng = ServeEngine(cfg, params, config=EngineConfig(
        n_slots=1, max_len=48, page_size=8, segment_len=8, max_new_cap=10,
        factor_cache=True))
    eng.submit(Request(rid=0, tokens=p1, max_new=10))
    eng.submit(Request(rid=1, tokens=p2, max_new=10))   # rides recycled slot
    outs = eng.run()
    solo = ServeEngine(cfg, params, config=EngineConfig(
        n_slots=1, max_len=48, page_size=8, segment_len=8, max_new_cap=10,
        factor_cache=True))
    solo.submit(Request(rid=1, tokens=p2, max_new=10))
    outs_solo = solo.run()
    np.testing.assert_array_equal(
        outs[1], outs_solo[1],
        err_msg="recycled slot leaked previous occupant's state")
    assert eng.cache.free_pages == eng.cache.n_pages - 1


def test_recycled_slot_isolation_drrl():
    """Same isolation property under the drrl policy: the recycled slot's
    first decision must not feed the previous occupant's rank into the
    policy features."""
    from repro.core.drrl import init_agent
    cfg = _drrl_cfg("drrl")
    fns = get_model(cfg)
    params = fns.init(RNG)
    policy = init_agent(jax.random.PRNGKey(7), cfg.rank, cfg.d_model)
    rnd = np.random.default_rng(4)
    p1 = rnd.integers(0, cfg.vocab_size, 13).astype(np.int32)
    p2 = rnd.integers(0, cfg.vocab_size, 10).astype(np.int32)

    def serve(reqs):
        eng = ServeEngine(cfg, params, policy, config=EngineConfig(
            n_slots=1, max_len=48, page_size=8, segment_len=8, max_new_cap=10,
            factor_cache=True))
        for r in reqs:
            eng.submit(r)
        return eng.run()

    outs = serve([Request(rid=0, tokens=p1, max_new=10),
                  Request(rid=1, tokens=p2, max_new=10)])
    outs_solo = serve([Request(rid=1, tokens=p2, max_new=10)])
    np.testing.assert_array_equal(outs[1], outs_solo[1])


# ---------------------------------------------------------------------------
# satellite fixes
# ---------------------------------------------------------------------------

def test_random_mode_folds_slot_into_key():
    """Two slots with identical K content at the same segment clock must
    not draw identical bucket sequences."""
    cfg = _drrl_cfg("random")
    decide = make_decide_fn(cfg)
    cache = PagedKVCache(cfg, 2, max_len=16, page_size=8)
    L, hkv, dh = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim()
    k = np.random.default_rng(0).normal(
        size=(L, 12, hkv, dh)).astype(np.float32)
    for slot in (0, 1):
        cache.allocate(slot, 12)
        cache.write_prefill(slot, jnp.asarray(k), jnp.asarray(k))
    draws = {0: [], 1: []}
    for slot in (0, 1):
        for t in range(8):
            (cache.ranks, cache.basis, cache.spectra, cache.kt_pool,
             _veto) = decide(
                cache.k_pool, cache.mass_pool, cache.kt_pool,
                jnp.asarray(cache.page_table),
                jnp.asarray(cache.lens, jnp.int32), cache.ranks,
                cache.basis, cache.spectra, np.int32(slot),
                np.bool_(False), np.int32(t))
            draws[slot].append(int(cache.ranks[slot]))
    assert draws[0] != draws[1], draws


def test_veto_uses_previous_segment_spectra():
    """The Eq. 9 transition veto must read the slot's persisted
    previous-decision spectra: fabricating a huge flat 'before' spectrum
    blows up the relative bound and freezes the slot at its previous rank,
    which comparing the current spectra against themselves never would."""
    cfg = _drrl_cfg("adaptive", energy_threshold=0.90, epsilon0=1.0)
    decide = make_decide_fn(cfg)
    L, hkv, dh = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim()
    cache = PagedKVCache(cfg, 1, max_len=16, page_size=8)
    k = np.random.default_rng(3).normal(
        size=(L, 12, hkv, dh)).astype(np.float32)
    cache.allocate(0, 12)
    cache.write_prefill(0, jnp.asarray(k), jnp.asarray(k))

    def run_decide(has_rank):
        return decide(cache.k_pool, cache.mass_pool, cache.kt_pool,
                      jnp.asarray(cache.page_table),
                      jnp.asarray(cache.lens, jnp.int32), cache.ranks,
                      cache.basis, cache.spectra, np.int32(0),
                      np.bool_(has_rank), np.int32(0))

    ranks, basis, spectra, kt, veto = run_decide(False)
    natural = int(ranks[0])
    # a first decision has no previous rank to veto against
    assert not bool(veto)
    # first decision persisted its layer-0 spectra
    assert float(jnp.abs(spectra[0]).max()) > 0.0
    # normal transition: same K, stored spectra == current -> no veto, the
    # slot re-chooses its natural rank even from a different prev rank
    cache.spectra = spectra
    cache.ranks = jnp.asarray([4 if natural != 4 else 16], jnp.int32)
    ranks2, _, _, _, veto2 = run_decide(True)
    assert int(ranks2[0]) == natural
    assert not bool(veto2)
    # fabricated huge flat previous spectrum -> relative bound >> eps_t ->
    # the veto keeps the previous rank, and reports the fire
    cache.spectra = jnp.full_like(cache.spectra, 1e8)
    ranks3, _, _, _, veto3 = run_decide(True)
    assert int(ranks3[0]) == int(cache.ranks[0]) != natural
    assert bool(veto3)
