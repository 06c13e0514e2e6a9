"""Async serving front door + multi-replica router (repro.serve.frontend).

Covers the layer above the continuous-batching core:
  * consumption parity: tokens delivered through a background stepping
    thread (sync iterator, ``async for``, ``result()``) are identical to
    an explicit ``step()`` loop, across dense/factor caches and the
    kernel/XLA decode paths,
  * cancellation mid-stream freezes the handle and releases its pages
    (refcount audit), pending cancellation never admits,
  * ``Engine.reset()`` / ``shutdown()`` with a live stepping thread:
    stranded consumers raise :class:`EngineStopped` instead of hanging,
    and the engine stays usable after reset,
  * FleetConfig validation; Router prefix-affinity dispatch sticks
    follow-ups to the warm replica and beats round-robin's hit-rate on
    a shared-prefix workload; a 2-replica fleet drains a bursty mixed
    workload completely with sane aggregate stats.
"""
import asyncio
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import RankConfig
from repro.models.api import get_model
from repro.serve import (Engine, EngineConfig, EngineStopped, FleetConfig,
                         FrontEnd, Router, SamplingParams)

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
              max_new_cap=8, prefill_chunk=8)
    kw.update(over)
    return Engine(cfg, params, config=EngineConfig(**kw))


def _prompts(n, lo=8, hi=14, seed=0):
    rnd = np.random.default_rng(seed)
    return [rnd.integers(0, 256, int(rnd.integers(lo, hi))).astype(np.int32)
            for _ in range(n)]


# ---------------------------------------------------------------------------
# consumption parity: front-door delivery == explicit step loop
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("factor,use_kernel", [
    (False, False), (True, False), (False, True), (True, True)],
    ids=["dense-xla", "factor-xla", "dense-kernel", "factor-kernel"])
def test_frontend_parity(setup, factor, use_kernel):
    """Sync iterator / ``async for`` / ``result()`` through the stepping
    thread match an explicit step() loop token-for-token."""
    cfg, params = setup
    prompts = _prompts(3, seed=1)
    sp = SamplingParams(max_new=6)

    ref_eng = _engine(cfg, params, factor_cache=factor,
                      use_kernel=use_kernel)
    ref_hs = [ref_eng.submit(p, sp) for p in prompts]
    ref_eng.run()
    ref = [h.result().tolist() for h in ref_hs]

    eng = _engine(cfg, params, factor_cache=factor, use_kernel=use_kernel)
    with FrontEnd(eng, idle_poll_s=0.01) as fe:
        h0, h1, h2 = (fe.submit(p, sp) for p in prompts)
        sync_toks = list(h0.tokens())

        async def consume(h):
            return [t async for t in h]

        async_toks = asyncio.run(consume(h1))
        batch = h2.result().tolist()
        assert fe.drain(30.0)
    assert sync_toks == ref[0]
    assert async_toks == ref[1]
    assert batch == ref[2]
    for h in (h0, h1, h2):
        assert h.done and h.ttft_s is not None and h.done_s is not None


def test_passive_iteration_never_steps(setup):
    """With a live driver the handle iterator must not call step() itself
    (passive consumption): steps counted == steps the thread ran."""
    cfg, params = setup
    eng = _engine(cfg, params)
    with FrontEnd(eng, idle_poll_s=0.01) as fe:
        h = fe.submit(_prompts(1, seed=2)[0], SamplingParams(max_new=6))
        calls = []
        orig = eng.step

        def counting_step():
            calls.append(threading.current_thread().name)
            return orig()

        eng.step = counting_step
        toks = list(h.tokens())
        eng.step = orig
    assert len(toks) == 6
    assert all(name.startswith("serve-frontend") for name in calls), \
        f"consumer thread stepped the engine itself: {set(calls)}"


# ---------------------------------------------------------------------------
# cancellation
# ---------------------------------------------------------------------------

def test_cancel_mid_stream_releases_pages(setup):
    cfg, params = setup
    eng = _engine(cfg, params, prefix_cache=True)
    with FrontEnd(eng, idle_poll_s=0.01) as fe:
        h = fe.submit(_prompts(1, seed=3)[0], SamplingParams(max_new=8))
        it = h.tokens()
        first = next(it)
        assert h.cancel()
        rest = list(it)                     # iterator ends, never blocks
        res = h.result()
        assert h.cancelled and h.done
        assert res.tolist() == [first] + rest
        assert not h.cancel()               # idempotent: already done
        assert fe.drain(30.0)
        # no token may arrive after the cancel froze the stream
        n = len(res)
        time.sleep(0.05)
        assert len(h.result()) == n
    # every page back on the free list / accounted to the prefix tree
    eng.core.cache.check_refs(eng.core.prefix.all_pages()
                              if eng.core.prefix else ())


def test_cancel_pending_request(setup):
    """A queued (never admitted) request cancels cleanly while the two
    slots are occupied; the fleet then drains the survivors."""
    cfg, params = setup
    eng = _engine(cfg, params)
    sp = SamplingParams(max_new=8)
    with FrontEnd(eng, idle_poll_s=0.01) as fe:
        live = [fe.submit(p, sp) for p in _prompts(2, seed=4)]
        queued = fe.submit(_prompts(1, seed=5)[0], sp)
        assert queued.cancel()
        assert queued.cancelled and queued.done
        assert queued.result().size == 0    # nothing was ever decoded
        assert fe.drain(30.0)
        for h in live:
            assert len(h.result()) == 8
    eng.core.cache.check_refs()


# ---------------------------------------------------------------------------
# reset / shutdown safety with a live stepping thread
# ---------------------------------------------------------------------------

def test_reset_strands_consumers_then_engine_reusable(setup):
    cfg, params = setup
    eng = _engine(cfg, params)
    fe = FrontEnd(eng, idle_poll_s=0.01)
    try:
        h = fe.submit(_prompts(1, seed=6)[0], SamplingParams(max_new=8))
        eng.reset()                          # live thread: must be safe
        with pytest.raises(EngineStopped):
            h.result()
        # the engine (and its thread) survive reset: serve again
        h2 = fe.submit(_prompts(1, seed=7)[0], SamplingParams(max_new=4))
        assert len(h2.result()) == 4
    finally:
        fe.shutdown(drain=False)


def test_shutdown_marks_handles_stopped(setup):
    cfg, params = setup
    eng = _engine(cfg, params)
    fe = FrontEnd(eng, idle_poll_s=0.01)
    h = fe.submit(_prompts(1, seed=8)[0], SamplingParams(max_new=8))
    fe.shutdown(drain=False)
    # either it finished in the drain window or it raises — never hangs
    try:
        list(h.tokens())
    except EngineStopped:
        pass
    with pytest.raises(EngineStopped):
        fe.submit(_prompts(1, seed=9)[0], SamplingParams(max_new=4))
    fe.shutdown()                            # idempotent


def test_step_error_propagates_as_engine_stopped(setup):
    cfg, params = setup
    eng = _engine(cfg, params)
    fe = FrontEnd(eng, idle_poll_s=0.01)

    def boom():
        raise RuntimeError("injected step failure")

    eng.step = boom
    h = fe.submit(_prompts(1, seed=10)[0], SamplingParams(max_new=4))
    with pytest.raises(EngineStopped):
        h.result()
    with pytest.raises(EngineStopped):
        fe.drain(5.0)
    fe.shutdown(drain=False)


# ---------------------------------------------------------------------------
# FleetConfig validation
# ---------------------------------------------------------------------------

def test_fleet_config_validation():
    with pytest.raises(ValueError, match="n_replicas"):
        FleetConfig(n_replicas=0)
    with pytest.raises(ValueError, match="routing"):
        FleetConfig(routing="random")
    with pytest.raises(ValueError, match="affinity_min_tokens"):
        FleetConfig(affinity_min_tokens=0)
    with pytest.raises(ValueError, match="depth_slack"):
        FleetConfig(depth_slack=-1)
    with pytest.raises(ValueError, match="idle_poll_s"):
        FleetConfig(idle_poll_s=0.0)


# ---------------------------------------------------------------------------
# router: prefix-affinity dispatch
# ---------------------------------------------------------------------------

def _fleet_cfg(**over):
    ekw = dict(n_slots=2, max_len=64, page_size=8, segment_len=8,
               max_new_cap=8, prefill_chunk=8, prefix_cache=True)
    fkw = dict(n_replicas=2, affinity_min_tokens=8, idle_poll_s=0.01)
    fkw.update(over)
    return FleetConfig(engine=EngineConfig(**ekw), **fkw)


def test_router_affinity_sticks_to_warm_replica(setup):
    cfg, params = setup
    rnd = np.random.default_rng(11)
    shared = rnd.integers(0, 256, 16).astype(np.int32)
    with Router(cfg, params, fleet=_fleet_cfg()) as router:
        lead = router.submit(np.concatenate([shared, [1, 2, 3, 4]]),
                             SamplingParams(max_new=4))
        lead.result()                        # warm exactly one replica
        hs = [router.submit(
                  np.concatenate([shared, rnd.integers(0, 256, 4)]),
                  SamplingParams(max_new=4)) for _ in range(4)]
        assert router.drain(30.0)
        st = router.stats()
        assert all(h.replica == lead.replica for h in hs), \
            f"affinity did not stick: {[h.replica for h in hs]}"
        assert st["route_kinds"]["affinity"] >= 4
        assert st["aggregate"]["hit_rate"] > 0.5


def test_router_affinity_beats_round_robin_hit_rate(setup):
    """Two prefix groups, follow-ups in shuffled order: affinity keeps
    each group on its warm replica; round-robin sprays and re-misses."""
    cfg, params = setup

    def drive(routing):
        rnd = np.random.default_rng(11)
        groups = [rnd.integers(0, 256, 16).astype(np.int32)
                  for _ in range(2)]
        with Router(cfg, params,
                    fleet=_fleet_cfg(routing=routing)) as router:
            sp = SamplingParams(max_new=4)
            leads = [router.submit(np.concatenate([g, rnd.integers(0, 256,
                                                                   4)]), sp)
                     for g in groups]
            for h in leads:
                h.result()
            # [0,0,1,1] provably misaligns a 2-replica rotation: strict
            # round-robin lands each group on each replica once (two
            # cold misses); affinity sticks all four to warm replicas
            for g in (0, 0, 1, 1):
                router.submit(np.concatenate([groups[g],
                                              rnd.integers(0, 256, 4)]), sp)
            assert router.drain(30.0)
            return router.stats()["aggregate"]["hit_rate"]

    assert drive("affinity") > drive("round_robin")


def test_router_overload_falls_back_to_least_loaded(setup):
    """An affinity hit on a replica deep beyond depth_slack is abandoned
    for the shallow replica: locality is not worth a convoy."""
    cfg, params = setup
    rnd = np.random.default_rng(12)
    shared = rnd.integers(0, 256, 16).astype(np.int32)
    fleet = _fleet_cfg(depth_slack=0, warmup=True)
    with Router(cfg, params, fleet=fleet) as router:
        lead = router.submit(np.concatenate([shared, [1, 2, 3]]),
                             SamplingParams(max_new=4))
        lead.result()
        warm = lead.replica
        # pile depth onto the warm replica only, bypassing the router
        backlog = [router.replicas[warm].submit(
                       rnd.integers(0, 256, 12).astype(np.int32),
                       SamplingParams(max_new=8)) for _ in range(4)]
        h = router.submit(np.concatenate([shared, [7, 8, 9]]),
                          SamplingParams(max_new=4))
        assert h.replica != warm, "routed into the convoy"
        assert router.stats()["route_kinds"]["least_loaded"] >= 1
        assert router.drain(30.0)
        for b in backlog:
            b.result()


def test_fleet_drains_bursty_mixed_workload(setup):
    """2 replicas x 2 slots, 10 mixed requests in one burst: everything
    completes, token counts add up, both replicas took work."""
    cfg, params = setup
    with Router(cfg, params, fleet=_fleet_cfg()) as router:
        rnd = np.random.default_rng(13)
        hs = [router.submit(p, SamplingParams(
                  max_new=int(rnd.integers(2, 8))))
              for p in _prompts(10, seed=13)]
        assert router.drain(60.0)
        total = sum(len(h.result()) for h in hs)
        st = router.stats()
        # each request's first token is emitted by its final prefill
        # chunk (a mixed step), which the decode counter excludes
        assert st["aggregate"]["tokens_decoded"] == total - len(hs)
        assert st["aggregate"]["depth"] == 0
        assert sorted(st["routed"]) != [0, sum(st["routed"])], \
            "one replica took the whole burst"
        assert st["aggregate"]["tok_per_s"] > 0
        for fe in router.replicas:
            fe.engine.core.cache.check_refs(
                fe.engine.core.prefix.all_pages())


_ROUTER_4DEV = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import json, sys
sys.path.insert(0, __SRC__)
import jax
import numpy as np
from repro.configs import get_config
from repro.configs.base import RankConfig
from repro.models.api import get_model
from repro.serve import EngineConfig, FleetConfig, Router, SamplingParams

cfg = get_config("drrl-paper", reduced=True).with_(rank=RankConfig(
    mode="adaptive", rank_grid=(4, 8, 12, 16), fixed_rank=8, segment_len=8))
params = get_model(cfg).init(jax.random.PRNGKey(0))
rnd = np.random.default_rng(0)
prompts = [rnd.integers(0, 256, int(rnd.integers(8, 20))).astype(np.int32)
           for _ in range(8)]

def run(n):
    router = Router(cfg, params, fleet=FleetConfig(
        engine=EngineConfig(n_slots=2, max_len=48, page_size=8,
                            segment_len=8, max_new_cap=8, prefill_chunk=8,
                            prefix_cache=True),
        n_replicas=n))
    hs = [router.submit(p, SamplingParams(max_new=8)) for p in prompts]
    outs = [h.result().tolist() for h in hs]
    placed = []
    for fe in router.replicas:
        core = fe.engine.core
        placed.append([str(d) for x in (core.params["embed"],
                                        core.cache.k_pool, core.cache.v_pool,
                                        core.cache.mass_pool, core.out_buf)
                       for d in x.devices()])
    router.shutdown()
    return outs, placed, router.routed

outs4, placed4, routed4 = run(4)
outs1, _, _ = run(1)
print(json.dumps({"n_dev": len(jax.local_devices()), "placed": placed4,
                  "routed": routed4, "same": outs4 == outs1,
                  "lens": [len(o) for o in outs4]}))
"""


@pytest.mark.dist
def test_router_places_each_replica_on_its_own_device():
    """On a 4-device host a 4-replica Router keeps each replica's params,
    page pools and output buffer on its own device, and serves every
    request exactly as a 1-replica Router does."""
    import json
    import os
    import subprocess
    import sys
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _ROUTER_4DEV.replace("__SRC__", repr(src))],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["n_dev"] == 4
    assert all(len(set(p)) == 1 for p in res["placed"]), res["placed"]
    assert len({p[0] for p in res["placed"]}) == 4, res["placed"]
    assert all(n > 0 for n in res["routed"]), res["routed"]
    assert res["lens"] == [8] * 8
    assert res["same"]
