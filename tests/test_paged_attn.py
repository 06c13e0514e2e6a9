"""The paged attention read of the fused serve step against its XLA read.

On a TPU with dense K the fused step attends with the Pallas kernel
``repro.kernels.paged_attn.paged_decode_attention``, which reads each
row's pages from the pools through the page table and stops at the row's
kv_end; everywhere else it gathers the pages and attends in XLA. Here the
platform check of ``paged_attention_selected`` is told it runs on a TPU,
so the kernel runs in interpret mode and is compared with the XLA read:

  * one fused step (``decode_step_paged``): logits, the written pools and
    the updated attention-mass pool, for pure decode and for a mixed
    chunk of prefill and decode rows with short chunks, at one kv head
    per q head (head_dim 64) and six (head_dim 128), with per-slot rank
    masks below r_keep, pages holding stale contents past kv_end, and a
    dead lane;
  * the engine: chunked admission and decode give the same tokens and
    the same per-segment ranks, and the ``serve.attn.*`` counters count
    the kernel's steps and pages; a speculative engine gives the same
    tokens.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import RankConfig
from repro.models import transformer
from repro.models.api import get_model
from repro.serve import EngineConfig, Request, ServeEngine

pytestmark = pytest.mark.serve

NS, PS, N_PP = 4, 16, 16          # 4 slots of 256 positions: two blocks of 8 pages


@pytest.fixture
def on_tpu(monkeypatch):
    """The fused step's attention is chosen as on a TPU."""
    monkeypatch.setattr(transformer, "_on_tpu", lambda: True)


def _step_inputs(cfg, chunk, mixed, rank_on, seed=0):
    rng = np.random.default_rng(seed)
    L, hkv, dh = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim()
    P, M = NS * N_PP + 1, N_PP * PS
    # every page holds stale contents: what lies past a row's kv_end is a
    # previous occupant's keys and values
    pools = [jnp.asarray(rng.normal(size=(L, P, PS, hkv * dh)), jnp.float32)
             for _ in range(2)]
    pt = jnp.asarray(rng.permutation(np.arange(1, P)).reshape(NS, N_PP),
                     jnp.int32)
    kw = dict(slot_lens=jnp.asarray([5, 130, 77, 200], jnp.int32),
              active=jnp.asarray([True, True, True, False]))  # a dead lane
    if mixed:
        # rows 0 and 2 mid-prefill (a full and a short chunk), 1 decoding
        kw.update(q_lens=jnp.asarray([chunk, 1, 3, 1], jnp.int32),
                  prefill_rows=jnp.asarray([True, False, True, False]))
    if rank_on:
        kw.update(
            basis=jnp.linalg.qr(jnp.asarray(
                rng.normal(size=(L, NS, hkv, dh, dh)), jnp.float32))[0],
            slot_ranks=jnp.asarray([dh // 4, dh // 2, dh, 3 * dh // 4],
                                   jnp.int32),
            mass_pool=jnp.asarray(rng.random((L, NS, M, hkv)), jnp.float32))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (NS, chunk)),
                       jnp.int32)
    return pools, pt, toks, kw


@pytest.mark.parametrize("heads,kv_heads,head_dim,chunk,mixed,rank_on", [
    (2, 2, 64, 1, False, True),      # pure decode, one kv head per q head
    (2, 2, 64, 16, True, True),      # mixed prefill + decode rows
    (6, 1, 128, 1, False, True),     # pure decode, six q heads a kv head
    (6, 1, 128, 16, True, True),
    (2, 2, 64, 16, True, False),     # rank off: no basis, no mass
])
def test_paged_step_matches_xla(monkeypatch, heads, kv_heads, head_dim,
                                chunk, mixed, rank_on):
    cfg = get_config("drrl-paper", reduced=True).with_(
        num_heads=heads, num_kv_heads=kv_heads, head_dim=head_dim,
        rank=RankConfig(mode="drrl" if rank_on else "off"))
    fns = get_model(cfg)
    params = fns.init(jax.random.PRNGKey(0))
    (kp, vp), pt, toks, kw = _step_inputs(cfg, chunk, mixed, rank_on)
    assert transformer.paged_attention_selected(
        cfg, chunk=chunk, max_len=N_PP * PS, page_size=PS,
        pool_dtype=kp.dtype, factored=False) is False    # on the CPU
    out = {}
    for paged in (False, True):
        monkeypatch.setattr(transformer, "_on_tpu", lambda p=paged: p)
        out[paged] = jax.jit(lambda kp, vp: fns.decode_step_paged(
            params, kp, vp, pt, toks, **kw))(kp, vp)
    (lg_x, pools_x), (lg_p, pools_p) = out[False], out[True]
    live = np.asarray(kw["active"])
    np.testing.assert_allclose(lg_p[live], lg_x[live], atol=2e-5, rtol=0)
    for name in ("k", "v"):
        # page 0 is the scratch page the dead lane and padding write to
        np.testing.assert_allclose(pools_p[name][:, 1:], pools_x[name][:, 1:],
                                   atol=2e-5, rtol=0)
    if rank_on:
        np.testing.assert_allclose(pools_p["mass"], pools_x["mass"],
                                   atol=2e-6, rtol=0)
    else:
        assert "mass" not in pools_p


def _engine_run(cfg, params, prompts, chunk=5, **ekw):
    eng = ServeEngine(cfg, params, config=EngineConfig(
        n_slots=3, max_len=N_PP * PS, page_size=PS, segment_len=8,
        max_new_cap=12, prefill_chunk=chunk, **ekw))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, tokens=p, max_new=12, arrival=2 * i))
    return eng.run(), eng


def _ranks(eng):
    return np.stack(eng.ranks_per_step())


def test_engine_paged_kernel_matches_xla(monkeypatch):
    """Four staggered requests through three slots (one recycled), chunks
    of 5 with remainders, two rank buckets live: the kernel's engine emits
    the XLA engine's tokens and ranks, step for step."""
    cfg = get_config("drrl-paper", reduced=True).with_(
        rank=RankConfig(mode="adaptive", rank_grid=(4, 8, 12, 16),
                        segment_len=8))
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    rnd = np.random.default_rng(0)
    prompts = [rnd.integers(0, cfg.vocab_size, s).astype(np.int32)
               for s in (13, 20, 9, 15)]
    outs_x, eng_x = _engine_run(cfg, params, prompts)
    monkeypatch.setattr(transformer, "_on_tpu", lambda: True)
    outs_p, eng_p = _engine_run(cfg, params, prompts)
    for i in range(len(prompts)):
        np.testing.assert_array_equal(outs_p[i], outs_x[i],
                                      err_msg=f"stream {i}")
    np.testing.assert_array_equal(_ranks(eng_p), _ranks(eng_x))
    assert eng_p.stats["mixed_steps"] > 0
    assert len({r for s in _ranks(eng_p) for r in s.tolist() if r >= 0}) > 1
    # every step of the kernel's engine ran it; the XLA engine's none
    assert eng_x.stats["attn.paged_steps"] == 0
    assert eng_x.stats["attn.pages_read"] == 0
    assert eng_p.stats["attn.paged_steps"] == eng_p.stats["steps"] > 0
    assert (eng_p.stats["attn.pages_spanned"]
            == eng_p.stats["steps"] * 3 * N_PP)
    # whole blocks of 8 pages, one per live row (no stream passes 128)
    assert 0 < eng_p.stats["attn.pages_read"] < (
        eng_p.stats["attn.pages_spanned"])
    assert eng_p.stats["attn.pages_read"] % 8 == 0


@pytest.mark.parametrize("mode", ["adaptive", "off"])
def test_speculative_engine_paged_kernel_matches_xla(monkeypatch, mode):
    """The speculative step's draft forwards (and, with the rank path off,
    its verify) read dense K through the kernel too: the same drafts
    accepted and the same tokens as the XLA engine."""
    cfg = get_config("drrl-paper", reduced=True).with_(
        rank=RankConfig(mode=mode, rank_grid=(4, 8, 12, 16),
                        segment_len=8))
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    rnd = np.random.default_rng(1)
    prompts = [rnd.integers(0, cfg.vocab_size, s).astype(np.int32)
               for s in (11, 18, 7)]
    spec = dict(speculative=True, draft_k=3, draft_rank_frac=0.5)
    outs_x, eng_x = _engine_run(cfg, params, prompts, **spec)
    monkeypatch.setattr(transformer, "_on_tpu", lambda: True)
    outs_p, eng_p = _engine_run(cfg, params, prompts, **spec)
    assert eng_p.stats["spec_steps"] > 0
    assert eng_p.stats["spec_accepted"] == eng_x.stats["spec_accepted"]
    for i in range(len(prompts)):
        np.testing.assert_array_equal(outs_p[i], outs_x[i],
                                      err_msg=f"stream {i}")


def test_paged_counters_reckon_whole_blocks(on_tpu):
    """``pages_read`` counts each live row's blocks up to its kv_end:
    rows whose kv_end, with this step's token, is 5, 130 and 300 read 1,
    2 and 3 blocks of 8 pages, against 3 x 32 pages spanned."""
    cfg = get_config("drrl-paper", reduced=True).with_(
        rank=RankConfig(mode="off"))
    eng = ServeEngine(cfg, get_model(cfg).init(jax.random.PRNGKey(0)),
                      config=EngineConfig(n_slots=3, max_len=512,
                                          page_size=16))
    assert eng._paged_attn == {False: True, True: True}
    eng.cache.lens[:] = (4, 129, 299)
    eng._count_paged_read([0, 1, 2], {})
    assert eng.stats["attn.paged_steps"] == 1
    assert eng.stats["attn.pages_read"] == (1 + 2 + 3) * 8
    assert eng.stats["attn.pages_spanned"] == 3 * 32
