"""Unified streaming serving API (repro.serve.api).

Covers the request/response surface on top of the continuous-batching
core: EngineConfig / SamplingParams validation (fail fast at submit, not
silent forever-queueing), RequestHandle streaming (iterator + callback)
vs batch results, per-request TTFT, seeded sampling reproducibility, and
one config surface shared by Engine and the core ServeEngine.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import RankConfig
from repro.models.api import get_model
from repro.serve import Engine, EngineConfig, SamplingParams
from repro.serve.scheduler import Request


pytestmark = pytest.mark.serve

RNG = jax.random.PRNGKey(0)


def _cfg(mode="adaptive"):
    cfg = get_config("drrl-paper", reduced=True)
    return cfg.with_(rank=RankConfig(mode=mode, rank_grid=(4, 8, 12, 16),
                                     fixed_rank=8, segment_len=8))


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    params = get_model(cfg).init(RNG)
    return cfg, params


def _engine(cfg, params, **over):
    kw = dict(n_slots=2, max_len=48, page_size=8, segment_len=8,
              max_new_cap=8, prefill_chunk=4)
    kw.update(over)
    return Engine(cfg, params, config=EngineConfig(**kw))


# ---------------------------------------------------------------------------
# validation: fail fast at submit / construction
# ---------------------------------------------------------------------------

def test_submit_validation_fail_fast(setup):
    cfg, params = setup
    eng = _engine(cfg, params)
    prompt = np.arange(8, dtype=np.int32)
    with pytest.raises(ValueError, match="negative arrival"):
        eng.submit(prompt, SamplingParams(max_new=4), arrival=-1)
    with pytest.raises(ValueError, match="max_new"):
        eng.submit(prompt, SamplingParams(max_new=9))   # > max_new_cap
    with pytest.raises(ValueError, match="cache positions"):
        # prompt + max_new exceeds a slot's page capacity: would queue
        # forever under the old surface, must raise at submit
        eng.submit(np.arange(44, dtype=np.int32), SamplingParams(max_new=8))
    with pytest.raises(ValueError, match="top_k"):
        eng.submit(prompt, SamplingParams(max_new=4, top_k=1000))
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(np.zeros((0,), np.int32), SamplingParams(max_new=4))
    # nothing above leaked into the queue
    assert not eng.core.sched.pending
    greedy_only = _engine(cfg, params, sampling=False)
    with pytest.raises(ValueError, match="sampling=False"):
        greedy_only.submit(prompt, SamplingParams(max_new=4,
                                                  temperature=0.5))


def test_params_validation():
    with pytest.raises(ValueError):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError):
        SamplingParams(top_k=-1)
    with pytest.raises(ValueError):
        SamplingParams(max_new=0)
    with pytest.raises(ValueError):
        EngineConfig(prefill_chunk=0)
    with pytest.raises(ValueError):
        Request(rid=0, tokens=np.arange(3), max_new=1, temperature=-1.0)


# ---------------------------------------------------------------------------
# streaming handles
# ---------------------------------------------------------------------------

def test_handle_streaming_matches_result(setup):
    cfg, params = setup
    eng = _engine(cfg, params)
    rnd = np.random.default_rng(0)
    p0, p1 = (rnd.integers(0, cfg.vocab_size, s).astype(np.int32)
              for s in (10, 13))
    seen = []
    h0 = eng.submit(p0, SamplingParams(max_new=8),
                    on_token=lambda i, t: seen.append((i, t)))
    h1 = eng.submit(p1, SamplingParams(max_new=8), arrival=2)
    streamed = list(h0.tokens())          # drives the engine until h0 done
    assert h0.done and len(streamed) == 8
    out = eng.run()                       # drain h1
    assert h1.done
    np.testing.assert_array_equal(streamed, h0.result())
    np.testing.assert_array_equal(out[h0.rid], h0.result())
    assert seen == list(enumerate(streamed))       # callback saw every token
    assert h0.ttft_s is not None and h0.ttft_s > 0
    assert h1.ttft_s is not None
    assert len(h1.result()) == 8
    assert set(eng.ttft()) == {h0.rid, h1.rid}


def test_streaming_matches_nonstreaming_run(setup):
    """A handle consumed incrementally and a handle read only at the end
    must hold identical tokens (per-step sync changes delivery, not
    content)."""
    cfg, params = setup
    prompt = np.random.default_rng(1).integers(
        0, cfg.vocab_size, 11).astype(np.int32)
    eng_a = _engine(cfg, params)
    toks_stream = list(eng_a.submit(prompt,
                                    SamplingParams(max_new=8)).tokens())
    eng_b = _engine(cfg, params)
    h = eng_b.submit(prompt, SamplingParams(max_new=8))
    eng_b.run()
    np.testing.assert_array_equal(toks_stream, h.result())


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

def test_seeded_sampling_reproducible_and_varied(setup):
    cfg, params = setup
    prompt = np.random.default_rng(2).integers(
        0, cfg.vocab_size, 9).astype(np.int32)

    def draw(seed):
        eng = _engine(cfg, params)
        h = eng.submit(prompt, SamplingParams(max_new=8, temperature=1.0,
                                              seed=seed))
        eng.run()
        return h.result()

    a, b, c = draw(7), draw(7), draw(8)
    np.testing.assert_array_equal(a, b)     # same seed -> same stream
    assert not np.array_equal(a, c)         # different seed -> different draw


def test_greedy_on_sampling_engine_matches_greedy_only(setup):
    """temperature == 0 rows take the plain argmax: a sampling-enabled
    engine serves greedy requests bitwise like the greedy-only build."""
    cfg, params = setup
    prompt = np.random.default_rng(3).integers(
        0, cfg.vocab_size, 12).astype(np.int32)
    outs = []
    for sampling in (True, False):
        eng = _engine(cfg, params, sampling=sampling)
        h = eng.submit(prompt, SamplingParams(max_new=8))
        eng.run()
        outs.append(h.result())
    np.testing.assert_array_equal(outs[0], outs[1])


def test_topk_masks_tail(setup):
    """top_k=1 sampling is argmax regardless of temperature."""
    cfg, params = setup
    prompt = np.random.default_rng(4).integers(
        0, cfg.vocab_size, 9).astype(np.int32)
    eng = _engine(cfg, params)
    h_greedy = eng.submit(prompt, SamplingParams(max_new=6))
    h_k1 = eng.submit(prompt, SamplingParams(max_new=6, temperature=2.0,
                                             top_k=1, seed=3))
    eng.run()
    np.testing.assert_array_equal(h_k1.result(), h_greedy.result())


def test_top_p_validation(setup):
    cfg, params = setup
    for bad in (0.0, -0.5, 1.2):
        with pytest.raises(ValueError, match="top_p"):
            SamplingParams(top_p=bad)
        with pytest.raises(ValueError, match="top_p"):
            Request(rid=0, tokens=np.arange(3), max_new=1, top_p=bad)
    greedy_only = _engine(cfg, params, sampling=False)
    with pytest.raises(ValueError, match="sampling=False"):
        greedy_only.submit(np.arange(8, dtype=np.int32),
                           SamplingParams(max_new=4, temperature=1.0,
                                          top_p=0.5))
    # the nucleus cut is a compiled-in full-vocab sort: engines that did
    # not opt in reject top_p requests instead of silently paying for it
    no_nucleus = _engine(cfg, params)
    with pytest.raises(ValueError, match="nucleus"):
        no_nucleus.submit(np.arange(8, dtype=np.int32),
                          SamplingParams(max_new=4, temperature=1.0,
                                         top_p=0.5))
    with pytest.raises(ValueError, match="nucleus"):
        Engine(cfg, params,
               config=EngineConfig(nucleus=True, sampling=False))


def test_core_and_api_engine_share_one_config(setup):
    """``ServeEngine(cfg, params)`` and ``Engine(cfg, params)`` build the
    same engine: both hold ``EngineConfig()``, and its chunk width and
    sampling switch reach the core — a 20-token prompt takes two mixed
    steps at the default chunk of 16, and a sampled request is served
    (not refused as it would be by a greedy-only step), identically."""
    from repro.serve import ServeEngine
    cfg, params = setup
    core = ServeEngine(cfg, params)
    api = Engine(cfg, params)
    assert core.config == api.config == api.core.config == EngineConfig()
    prompt = np.random.default_rng(15).integers(
        0, cfg.vocab_size, 20).astype(np.int32)
    outs = []
    for c in (core, api.core):
        c.submit(Request(rid=0, tokens=prompt, max_new=4, temperature=0.9,
                         top_k=8, seed=3))
        outs.append(c.run()[0])
        assert c.stats["mixed_steps"] == 2
        assert c.stats["tokens_decoded"] == 3
    np.testing.assert_array_equal(outs[0], outs[1])


def test_top_p_tiny_nucleus_is_greedy(setup):
    """top_p -> 0 shrinks the nucleus to the single most likely token, so
    hot sampling collapses to argmax."""
    cfg, params = setup
    prompt = np.random.default_rng(11).integers(
        0, cfg.vocab_size, 9).astype(np.int32)
    eng = _engine(cfg, params, nucleus=True)
    h_greedy = eng.submit(prompt, SamplingParams(max_new=6))
    h_p = eng.submit(prompt, SamplingParams(max_new=6, temperature=2.0,
                                            top_p=1e-9, seed=5))
    eng.run()
    np.testing.assert_array_equal(h_p.result(), h_greedy.result())


def test_top_p_one_bypasses_nucleus_bitwise(setup):
    """top_p == 1 rows take the exact pre-top-p sampling path: the same
    stream draws bitwise identically on a nucleus-enabled engine and on
    one compiled without the cut (same temperature/top_k/seed)."""
    cfg, params = setup
    prompt = np.random.default_rng(12).integers(
        0, cfg.vocab_size, 10).astype(np.int32)

    def draw(**over):
        eng = _engine(cfg, params, **over)
        h = eng.submit(prompt, SamplingParams(max_new=8, temperature=0.9,
                                              top_k=12, seed=21))
        eng.run()
        return h.result()

    np.testing.assert_array_equal(draw(), draw(nucleus=True))


def test_top_p_draws_stay_inside_nucleus(setup):
    """In-graph nucleus math vs a NumPy oracle: every sampled token must
    lie in the smallest probability-sorted set reaching top_p mass, for a
    mixed batch (greedy / top-k / top-p / combined) in ONE call."""
    import jax.numpy as jnp
    cfg, params = setup
    eng = _engine(cfg, params, nucleus=True)
    core = eng.core
    rnd = np.random.default_rng(13)
    ns, V = 4, cfg.vocab_size
    logits = rnd.normal(scale=3.0, size=(ns, V)).astype(np.float32)
    temps = np.asarray([0.0, 1.0, 0.8, 1.2], np.float32)
    topks = np.asarray([0, 16, 0, 8], np.int32)
    topps = np.asarray([1.0, 1.0, 0.7, 0.5], np.float32)
    seeds = np.asarray([1, 2, 3, 4], np.uint32)

    def nucleus(row):
        lg = logits[row].copy()
        if topks[row] > 0:
            thr = np.sort(lg)[::-1][topks[row] - 1]
            lg[lg < thr] = -np.inf
        pr = np.exp(lg / max(temps[row], 1e-6)
                    - np.max(lg / max(temps[row], 1e-6)))
        pr /= pr.sum()
        order = np.argsort(-pr)
        cum = np.cumsum(pr[order])
        n_keep = int(np.searchsorted(cum, topps[row]) + 1)
        return set(order[:n_keep].tolist())

    for pos in range(6):
        tok = np.asarray(core._select_token(
            jnp.asarray(logits), jnp.full((ns,), pos, jnp.int32),
            jnp.asarray(temps), jnp.asarray(topks), jnp.asarray(topps),
            jnp.asarray(seeds)))
        assert tok[0] == np.argmax(logits[0])          # greedy row
        for row in range(1, ns):
            assert int(tok[row]) in nucleus(row), (pos, row)


def test_top_p_parity_chunked_vs_oneshot(setup):
    """The nucleus cut runs through the same (seed, output index) PRNG
    fold: a top-p stream draws identically under a narrow chunk and a
    covering one (the whole prompt in one mixed step)."""
    cfg, params = setup
    prompt = np.random.default_rng(14).integers(
        0, cfg.vocab_size, 11).astype(np.int32)

    def run(chunk):
        eng = _engine(cfg, params, prefill_chunk=chunk, nucleus=True)
        h = eng.submit(prompt, SamplingParams(max_new=8, temperature=0.8,
                                              top_k=20, top_p=0.8, seed=9))
        eng.run()
        return h.result()

    np.testing.assert_array_equal(run(4), run(len(prompt)))


def test_late_consumer_backfills_gap(setup):
    """A consumer attaching after tokens were already emitted (another
    handle's streaming flipped the sync on mid-run) gets a contiguous
    stream via device-buffer backfill, never a garbled one."""
    cfg, params = setup
    rnd = np.random.default_rng(7)
    pa = rnd.integers(0, cfg.vocab_size, 10).astype(np.int32)
    pb = rnd.integers(0, cfg.vocab_size, 7).astype(np.int32)
    eng = _engine(cfg, params)
    eng.submit(pa, SamplingParams(max_new=8))
    hb = eng.submit(pb, SamplingParams(max_new=8), arrival=1)
    for _ in range(6):
        eng.step()                       # hb mid-flight, no consumer yet
    got = list(hb.tokens())              # late attach
    np.testing.assert_array_equal(got, hb.result())
    eng.run()


def test_step_loop_accrues_decode_time_and_releases_sync(setup):
    """Iterator/step-driven loops must accrue stats['decode_s'] (review
    fix: only run() used to account wall time, inflating tok/s), and the
    per-step token sync must switch off with the last streaming
    consumer."""
    cfg, params = setup
    prompt = np.random.default_rng(8).integers(
        0, cfg.vocab_size, 9).astype(np.int32)
    eng = _engine(cfg, params)
    h = eng.submit(prompt, SamplingParams(max_new=8))
    list(h.tokens())
    assert eng.stats["decode_s"] > 0.0
    assert eng.core._stream_sync is False     # consumer finished
    eng.reset()
    assert eng.core._stream_sync is False


def test_ttft_is_first_token_not_completion(setup):
    """A non-streaming handle's ttft_s must come from the engine's
    token-0 timestamp, not from result delivery at completion (review
    fix: the finish-time backfill used to stamp token 0 with the full
    generation wall)."""
    import time
    cfg, params = setup
    prompt = np.random.default_rng(9).integers(
        0, cfg.vocab_size, 10).astype(np.int32)
    eng = _engine(cfg, params)
    h = eng.submit(prompt, SamplingParams(max_new=8))
    eng.warmup()
    t0 = time.perf_counter()
    eng.run()
    wall = time.perf_counter() - t0
    # token 0 lands after ~3 chunk steps out of ~11 total steps: TTFT must
    # be well below the full generation wall
    assert h.ttft_s is not None and h.ttft_s < 0.8 * wall, (h.ttft_s, wall)


def test_on_token_callback_may_reenter_engine(setup):
    """An on_token callback runs under the step lock; it must be able to
    drive the engine itself (submit a follow-up and block on its result)
    — the lock is reentrant, recursing instead of deadlocking."""
    cfg, params = setup
    rnd = np.random.default_rng(15)
    pa = rnd.integers(0, cfg.vocab_size, 9).astype(np.int32)
    pb = rnd.integers(0, cfg.vocab_size, 7).astype(np.int32)
    eng = _engine(cfg, params)
    follow = {}

    def cb(idx, tok):
        if idx == 2 and "h" not in follow:
            follow["h"] = eng.submit(pb, SamplingParams(max_new=4))
            follow["out"] = follow["h"].result()     # re-enters step()

    ha = eng.submit(pa, SamplingParams(max_new=6), on_token=cb)
    eng.run()
    assert ha.done and follow["h"].done
    solo = _engine(cfg, params)
    hb = solo.submit(pb, SamplingParams(max_new=4))
    solo.run()
    np.testing.assert_array_equal(follow["out"], hb.result())


def test_tokens_on_finished_handle_keeps_sync_free_loop(setup):
    """Iterating tokens() on an already-finished request must not flip
    the engine into permanent per-step host syncing (review fix)."""
    cfg, params = setup
    prompt = np.random.default_rng(10).integers(
        0, cfg.vocab_size, 9).astype(np.int32)
    eng = _engine(cfg, params)
    h = eng.submit(prompt, SamplingParams(max_new=6))
    eng.run()
    got = list(h.tokens())                  # post-hoc read
    np.testing.assert_array_equal(got, h.result())
    assert eng.core._stream_sync is False
    assert not eng._streaming
