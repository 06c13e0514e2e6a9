"""Observability layer (repro.obs).

Covers the layer from primitives up through the serving stack:
  * metrics primitives: counter/gauge/histogram semantics, percentile
    interpolation + clamping, kind-mismatch rejection, the StatsView
    dict shim (reads, ``+=`` writes, reset-by-rebind), and the fleet
    rollup (counters sum, histograms merge bucket-wise, bound mismatch
    rejected),
  * exporter formats: Prometheus text exposition shape, Chrome trace
    documents validate against the schema subset and survive a JSON
    round-trip (the validator itself is exercised on broken docs),
  * flight recorder: bounded ring, dump files parse, no-directory and
    crash paths never raise,
  * engine integration: metrics + tracing ON is token-identical to OFF
    (greedy/factored and seeded-sampled/dense), request counters and
    TTFT samples line up with the workload, rank telemetry is sane,
  * FrontEnd integration: a raising step dumps the flight ring with
    reason "step_exception" before handles are stopped; concurrent
    exporter reads during background stepping never trip the writer.
"""
import json
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import RankConfig
from repro.models.api import get_model
from repro.obs import (PHASES, FlightRecorder, Gauge, Histogram,
                       MetricsRegistry, SpanTracer, StatsView, Stopwatch,
                       aggregate, aggregate_registry, validate_chrome_trace)
from repro.serve import (Engine, EngineConfig, EngineStopped, FrontEnd,
                         SamplingParams)

pytestmark = pytest.mark.serve

RNG = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------------

def test_counter_gauge_basics():
    r = MetricsRegistry()
    c = r.counter("toks")
    c.inc()
    c.inc(5)
    assert c.get() == 6 and r.get("toks") is c
    g = r.gauge("depth")
    g.set(3)
    g.set(1)
    assert g.get() == 1
    # get-or-create returns the same object; kind mismatch is an error
    assert r.counter("toks") is c
    with pytest.raises(TypeError):
        r.gauge("toks")
    with pytest.raises(TypeError):
        r.histogram("depth")
    c.zero()
    assert c.get() == 0
    snap = r.snapshot()
    assert snap == {"toks": 0, "depth": 1}


def test_histogram_percentiles_and_clamp():
    h = Histogram("lat", bounds=[0.001, 0.01, 0.1, 1.0])
    for v in [0.002, 0.003, 0.004, 0.005, 0.05, 0.5]:
        h.observe(v)
    assert h.count == 6
    assert h.mean() == pytest.approx(sum([0.002, 0.003, 0.004, 0.005,
                                          0.05, 0.5]) / 6)
    # percentiles are interpolated but always clamped to [vmin, vmax]
    for q in (0, 25, 50, 90, 99, 100):
        assert h.vmin <= h.percentile(q) <= h.vmax
    assert h.percentile(50) <= 0.01    # 4 of 6 samples in (0.001, 0.01]
    # overflow bucket: above the top bound still counted, clamped to vmax
    h.observe(50.0)
    assert h.count == 7 and h.percentile(100) == 50.0
    empty = Histogram("e", bounds=[1.0])
    assert empty.percentile(50) == 0.0 and empty.export()["min"] is None


def test_statsview_dict_shim():
    r = MetricsRegistry()
    sv = StatsView(r, {"steps": 0, "decode_s": 0.0, "eff_draft_k": 4},
                   gauges=("eff_draft_k",))
    sv["steps"] += 3
    sv["decode_s"] += 0.25
    sv["eff_draft_k"] = 2
    assert sv["steps"] == 3 and dict(sv) == {"steps": 3, "decode_s": 0.25,
                                             "eff_draft_k": 2}
    assert len(sv) == 3 and set(sv) == {"steps", "decode_s", "eff_draft_k"}
    # the view writes through to the registry (and respects gauge kinds)
    assert r.get("serve.steps").value == 3
    assert isinstance(r.get("serve.eff_draft_k"), Gauge)
    assert not isinstance(r.get("serve.steps"), Gauge)
    with pytest.raises(TypeError):
        del sv["steps"]
    # re-binding the same keys (engine reset) re-zeroes to init values
    sv2 = StatsView(r, {"steps": 0, "decode_s": 0.0, "eff_draft_k": 4},
                    gauges=("eff_draft_k",))
    assert dict(sv2) == {"steps": 0, "decode_s": 0.0, "eff_draft_k": 4}
    assert r.get("serve.steps").value == 0


def test_statsview_lazy_keys_refresh_only_when_read():
    r = MetricsRegistry()
    calls = []

    def refresh():
        calls.append(1)
        r.get("serve.rank.vetoes").inc(2)

    sv = StatsView(r, {"decides": 0, "rank_vetoes": 0},
                   names={"rank_vetoes": "serve.rank.vetoes"},
                   refresh=refresh, lazy=("rank_vetoes",))
    sv["decides"] += 5                     # other keys never refresh
    assert sv["decides"] == 5 and calls == []
    assert sv["rank_vetoes"] == 2 and len(calls) == 1
    assert dict(sv) == {"decides": 5, "rank_vetoes": 6}
    assert r.get("serve.rank.vetoes").value == 6


def test_aggregate_fleet_rollup():
    regs = []
    for n in (2, 5):
        r = MetricsRegistry()
        r.counter("toks").inc(n)
        r.gauge("depth").set(n)
        h = r.histogram("lat", bounds=[1.0, 10.0])
        h.observe(float(n))
        regs.append(r)
    regs[0].counter("only_a").inc(7)           # absent from replica 1
    merged = aggregate(regs)
    assert merged["toks"] == 7 and merged["depth"] == 7
    assert merged["only_a"] == 7
    assert merged["lat"]["count"] == 2 and merged["lat"]["sum"] == 7.0
    assert merged["lat"]["min"] == 2.0 and merged["lat"]["max"] == 5.0
    # the rollup is a detached copy: mutating it leaves shards alone
    out = aggregate_registry(regs)
    out.counter("toks").inc(100)
    assert regs[0].counter("toks").value == 2
    # histogram bound mismatch is a structural error, not a silent merge
    bad = MetricsRegistry()
    bad.histogram("lat", bounds=[1.0, 99.0]).observe(1.0)
    with pytest.raises(TypeError):
        aggregate_registry([regs[0], bad])


def test_prometheus_text_format():
    r = MetricsRegistry()
    r.counter("serve.tokens_decoded").inc(12)
    h = r.histogram("serve.ttft_s", bounds=[0.1, 1.0])
    h.observe(0.05)
    h.observe(0.5)
    text = r.prometheus_text("repro")
    lines = text.strip().split("\n")
    assert "# TYPE repro_serve_tokens_decoded counter" in lines
    assert "repro_serve_tokens_decoded 12" in lines
    assert "# TYPE repro_serve_ttft_s histogram" in lines
    # cumulative buckets + +Inf + sum/count
    assert 'repro_serve_ttft_s_bucket{le="0.1"} 1' in lines
    assert 'repro_serve_ttft_s_bucket{le="1"} 2' in lines
    assert 'repro_serve_ttft_s_bucket{le="+Inf"} 2' in lines
    assert "repro_serve_ttft_s_count 2" in lines


def test_stopwatch_disabled_is_none():
    assert Stopwatch(False).stop() is None
    sw = Stopwatch()
    assert sw.stop() >= 0.0


# ---------------------------------------------------------------------------
# tracer + validator
# ---------------------------------------------------------------------------

def test_tracer_emits_valid_round_trippable_trace():
    tr = SpanTracer(pid=3, capacity=100)
    tr.async_begin("request", 7, args={"rid": 7})
    tr.instant("first_token", tid=1, cat="request")
    tr.complete("dispatch", tr.now_us(), 12.5, tid=1000, cat="phase")
    tr.async_end("request", 7, args={"reason": "eos"})
    doc = tr.chrome_trace(metadata={"engine_id": 3})
    assert validate_chrome_trace(doc) == []
    rt = json.loads(json.dumps(doc))
    assert rt == doc and rt["otherData"]["engine_id"] == 3
    # capacity bound: overflow drops (counted), never grows the buffer
    small = SpanTracer(capacity=2)
    for _ in range(5):
        small.instant("x")
    assert len(small.events) == 2 and small.dropped == 3
    small.clear()
    assert small.events == [] and small.dropped == 0


def test_tracer_origin_maps_event_times_to_perf_counter():
    tr = SpanTracer()
    assert tr._t0 == tr.origin_s
    t0 = time.perf_counter()
    tr.instant("x")
    t1 = time.perf_counter()
    at = tr.origin_s + tr.events[-1]["ts"] * 1e-6
    assert t0 - 1e-9 <= at <= t1 + 1e-9
    tr.clear()
    assert tr._t0 == tr.origin_s >= t1


def test_trace_validator_rejects_malformed():
    assert validate_chrome_trace([]) == ["document is not a JSON object"]
    assert validate_chrome_trace({}) == ["traceEvents missing or not a list"]
    bad_ph = {"traceEvents": [{"name": "x", "ph": "Z", "pid": 0, "tid": 0,
                               "ts": 0.0}]}
    assert any("bad ph" in e for e in validate_chrome_trace(bad_ph))
    no_dur = {"traceEvents": [{"name": "x", "ph": "X", "pid": 0, "tid": 0,
                               "ts": 0.0}]}
    assert any("dur" in e for e in validate_chrome_trace(no_dur))
    orphan_end = {"traceEvents": [{"name": "r", "ph": "e", "id": "1",
                                   "cat": "request", "pid": 0, "tid": 0,
                                   "ts": 0.0}]}
    assert any("end without begin" in e
               for e in validate_chrome_trace(orphan_end))


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_recorder_ring_and_dump(tmp_path):
    fr = FlightRecorder(4, str(tmp_path), name="t")
    for i in range(10):
        fr.record("tick", i=i)
    assert len(fr.events) == 4 and fr.n_recorded == 10
    assert [e["i"] for e in fr.events] == [6, 7, 8, 9]   # newest survive
    path = fr.dump("unit_test", metrics={"toks": 3},
                   error=RuntimeError("boom"))
    with open(path) as f:
        doc = json.load(f)
    assert doc["reason"] == "unit_test" and doc["events_recorded"] == 10
    assert [e["i"] for e in doc["events"]] == [6, 7, 8, 9]
    assert doc["metrics"] == {"toks": 3}
    assert "boom" in doc["error"]
    # no directory configured: recording works, dump is a silent no-op
    off = FlightRecorder(4, None)
    off.record("tick")
    assert off.dump("nowhere") is None


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def setup():
    cfg = get_config("drrl-paper", reduced=True).with_(
        rank=RankConfig(mode="adaptive", rank_grid=(4, 8, 12, 16),
                        fixed_rank=8, segment_len=8))
    return cfg, get_model(cfg).init(RNG)


def _prompts(n, seed=0, lo=8, hi=14):
    rnd = np.random.default_rng(seed)
    return [rnd.integers(0, 256, int(rnd.integers(lo, hi))).astype(np.int32)
            for _ in range(n)]


def _run(cfg, params, sps, prompts, *, obs_trace, sampling, factor,
         flight_dir=None):
    eng = Engine(cfg, params, config=EngineConfig(
        n_slots=2, max_len=48, page_size=8, segment_len=8, max_new_cap=8,
        prefill_chunk=8, factor_cache=factor, sampling=sampling,
        obs_trace=obs_trace, flight_dir=flight_dir))
    hs = [eng.submit(p, sp) for p, sp in zip(prompts, sps)]
    eng.run()
    return eng, {h.rid: h.result().tolist() for h in hs}


@pytest.mark.parametrize("factor,sampling", [(True, False), (False, True)],
                         ids=["factor-greedy", "dense-sampled"])
def test_obs_on_off_token_parity(setup, factor, sampling, tmp_path):
    """Tracing + metrics ON must not change a single emitted token, and
    the exports must describe the workload exactly."""
    cfg, params = setup
    prompts = _prompts(3, seed=1)
    if sampling:
        sps = [SamplingParams(max_new=6, temperature=0.8, top_k=8, seed=i)
               for i in range(3)]
    else:
        sps = [SamplingParams(max_new=6) for _ in range(3)]
    _, ref = _run(cfg, params, sps, prompts, obs_trace=False,
                  sampling=sampling, factor=factor)
    eng, out = _run(cfg, params, sps, prompts, obs_trace=True,
                    sampling=sampling, factor=factor,
                    flight_dir=str(tmp_path))
    assert out == ref

    snap = eng.obs.snapshot()
    m = snap["metrics"]
    assert m["requests.admitted"] == 3 and m["requests.finished"] == 3
    assert m["requests.cancelled"] == 0
    assert m["serve.ttft_s"]["count"] == 3
    assert m["serve.tokens_decoded"] == eng.stats["tokens_decoded"]
    assert snap["trace"]["enabled"] and snap["trace"]["dropped"] == 0

    doc = eng.obs.chrome_trace()
    assert validate_chrome_trace(doc) == []
    assert json.loads(json.dumps(doc)) == doc
    evs = doc["traceEvents"]
    assert sum(e["ph"] == "b" for e in evs) == 3    # one span per request
    assert sum(e["ph"] == "e" for e in evs) == 3
    phases = {e["name"] for e in evs if e.get("cat") == "phase"}
    assert phases == {"schedule", "admit", "decide", "dispatch", "fetch",
                      "deliver"}

    prom = eng.obs.prometheus()
    assert "# TYPE repro_requests_admitted counter" in prom
    assert "repro_requests_admitted 3" in prom
    assert 'repro_serve_ttft_s_bucket{le="+Inf"} 3' in prom

    tel = eng.obs.rank_telemetry(eng.core)
    assert 0 < tel["steps_recorded"] <= eng.stats["steps"]
    assert tel["decisions"] == eng.stats["decides"] > 0
    assert tel["veto_fires"] >= 0 and tel["per_layer_uniform"]
    grid = set(cfg.rank.rank_grid) | {-1}
    assert all(v in grid for row in tel["kept_rank"] for v in row)


@pytest.mark.parametrize("obs_trace", [False, True],
                         ids=["tracing-off", "tracing-on"])
def test_phase_seconds_are_always_counted(setup, obs_trace):
    """The step phases' seconds accumulate with span tracing off too; with
    it on, each phase's counter is the sum of its X events."""
    cfg, params = setup
    eng, _ = _run(cfg, params, [SamplingParams(max_new=6)] * 3,
                  _prompts(3, seed=4), obs_trace=obs_trace, sampling=False,
                  factor=True)
    m = eng.obs.snapshot()["metrics"]
    secs = {p: m[f"serve.phase.{p}_s"] for p in PHASES}
    assert secs == {p: eng.stats[f"phase.{p}_s"] for p in PHASES}
    assert secs["dispatch"] > 0 and secs["deliver"] > 0
    evs = eng.obs.chrome_trace()["traceEvents"]
    for p in PHASES:
        traced = sum(e["dur"] for e in evs
                     if e.get("cat") == "phase" and e["name"] == p) * 1e-6
        assert traced == (pytest.approx(secs[p], rel=1e-9, abs=1e-12)
                          if obs_trace else 0.0)


def test_step_gauges_follow_the_slots(setup):
    cfg, params = setup
    eng = Engine(cfg, params, config=EngineConfig(
        n_slots=2, max_len=48, page_size=8, segment_len=8, max_new_cap=8,
        prefill_chunk=8))
    for p in _prompts(3, seed=5):
        eng.submit(p, SamplingParams(max_new=4))
    m = eng.obs.registry
    for _ in range(3):
        eng.step()
        live = [st for st in eng.core.sched.slots if st.active]
        assert m.get("slots.live").value == len(live) == 2
        assert m.get("queue.depth").value == 1
        assert m.get("kv.positions_live").value == sum(
            st.prefilled + st.n_out for st in live) > 0
    eng.run()
    assert [m.get(g).value for g in ("slots.live", "queue.depth",
                                     "kv.positions_live")] == [0, 0, 0]


def _kept_rank_sum(series, seg):
    """Sum of the ranks chosen at segment decisions, from the kept-rank
    series alone: a request's run of live steps on a slot decides at
    its decode steps 0, seg, 2 seg, ..."""
    total = 0
    for col in np.asarray(series).T:
        live = np.append(col >= 0, False)
        starts = np.flatnonzero(live & ~np.insert(live[:-1], 0, False))
        for a in starts:
            b = a + np.argmin(live[a:])
            total += int(col[a:b:seg].sum())
    return total


def test_rank_counters_match_the_decisions(setup):
    """``rank_chosen_sum`` and ``rank_vetoes`` count what each decision
    returned, read through ``dict(stats)`` as a harness reads them; they
    agree with ``rank_telemetry``; the loop never drains them, and a
    steady run with them builds no new executable."""
    from repro.analysis.sanitizer import CompileCounter, guard_steps
    cfg, params = setup
    # random draws keyed by (slot, clock): the slots' ranks differ, and
    # a tight Eq. 9 threshold vetoes some switches
    cfg = cfg.with_(rank=RankConfig(mode="random", rank_grid=(4, 8, 12),
                                    segment_len=4, epsilon0=0.3))
    eng = Engine(cfg, params, config=EngineConfig(
        n_slots=2, max_len=64, page_size=8, segment_len=4, max_new_cap=24,
        prefill_chunk=8))
    core = eng.core
    assert core.cache.factored
    seen, drains = [], []
    decide, drain = core._decide, core._drain_rank_counters

    def recording(*a):
        out = decide(*a)
        seen.append((out[0], int(a[8]), out[4]))
        return out

    def counting():
        drains.append(core.stats["steps"])
        drain()

    def serve(seed):
        for p in _prompts(3, seed=seed, lo=10, hi=20):
            eng.submit(p, SamplingParams(max_new=24))
        eng.run()

    core._decide, core._drain_rank_counters = recording, counting
    eng.reset()                        # the stats view takes the wrapper
    serve(seed=6)
    assert drains == []                # the loop itself never drains
    before = dict(eng.stats)
    got = [(int(np.asarray(r)[s]), bool(v)) for r, s, v in seen]
    assert before["decides"] == len(got) >= 12
    assert before["rank_chosen_sum"] == sum(g for g, _ in got)
    assert before["rank_vetoes"] == sum(v for _, v in got) >= 1
    tel = eng.obs.rank_telemetry(core)
    assert tel["veto_fires"] == before["rank_vetoes"]
    assert _kept_rank_sum(tel["kept_rank"], 4) == before["rank_chosen_sum"]
    m = eng.obs.snapshot()["metrics"]
    assert m["serve.rank.chosen_sum"] == before["rank_chosen_sum"]
    assert m["serve.rank.vetoes"] == before["rank_vetoes"]

    # steady state: same shapes, fresh prompts, fused steps under a
    # disallow transfer guard; reading the counters compiles nothing
    eng.reset()
    seen.clear()
    drains.clear()
    guard_steps(core)
    counter = CompileCounter()
    with counter.attached():
        serve(seed=7)
        assert drains == []
        after = dict(eng.stats)
    assert counter.count == 0, counter.messages
    got = [(int(np.asarray(r)[s]), bool(v)) for r, s, v in seen]
    assert after["decides"] == len(got) >= 12
    assert after["rank_chosen_sum"] == sum(g for g, _ in got)
    assert after["rank_vetoes"] == sum(v for _, v in got)


def test_frontend_step_exception_dumps_flight_ring(setup, tmp_path):
    cfg, params = setup
    eng = Engine(cfg, params, config=EngineConfig(
        n_slots=2, max_len=48, page_size=8, segment_len=8, max_new_cap=8,
        prefill_chunk=8, flight_dir=str(tmp_path)))

    def boom():
        raise RuntimeError("injected step failure")

    eng.core.step = boom
    fe = FrontEnd(eng, idle_poll_s=0.01, warmup=False)
    try:
        # the thread may die before or after submit returns — the raise
        # surfaces at whichever call touches the dead front end first
        with pytest.raises(EngineStopped):
            h = fe.submit(_prompts(1, seed=2)[0], SamplingParams(max_new=4))
            h.result()
    finally:
        fe.shutdown(drain=False)
    dumps = sorted(tmp_path.glob("flight_*.json"))
    assert dumps, "no flight dump written on step exception"
    with open(dumps[0]) as f:
        doc = json.load(f)
    assert doc["reason"] == "step_exception"
    assert "injected step failure" in doc["error"]
    assert "metrics" in doc


def test_registry_reads_safe_under_background_stepping(setup):
    """Exporters are documented as any-thread-safe: hammer them from a
    reader thread while the FrontEnd's stepping thread is writing."""
    cfg, params = setup
    eng = Engine(cfg, params, config=EngineConfig(
        n_slots=2, max_len=48, page_size=8, segment_len=8, max_new_cap=8,
        prefill_chunk=8, obs_trace=True))
    stop = threading.Event()
    errors, reads = [], [0]

    def reader():
        try:
            while not stop.is_set():
                snap = eng.obs.snapshot()
                assert snap["metrics"]["requests.admitted"] >= 0
                eng.obs.prometheus()
                json.dumps(eng.obs.chrome_trace())
                reads[0] += 1
        except Exception as e:   # surfaced after join — threads can't fail a test
            errors.append(e)

    t = threading.Thread(target=reader, daemon=True)
    with FrontEnd(eng, idle_poll_s=0.01) as fe:
        t.start()
        hs = [fe.submit(p, SamplingParams(max_new=6))
              for p in _prompts(4, seed=3)]
        outs = [h.result() for h in hs]
    stop.set()
    t.join(timeout=5)
    assert not errors and reads[0] > 0
    assert all(len(o) == 6 for o in outs)
    assert eng.obs.snapshot()["metrics"]["requests.finished"] == 4
