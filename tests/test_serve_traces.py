"""Serving-trace subsystem: recorder round-trip, schema versioning,
workload determinism, and the offline-trained policy closing the loop.

* recorder round-trip — records written through the engine hook land on
  disk exactly (shard + manifest), and two recording runs over the same
  seeded workload produce identical traces (column-for-column);
* schema versioning — TraceReader rejects unknown versions loudly and a
  missing manifest raises FileNotFoundError;
* sharding — records spill across shards at shard_size and concatenate
  back in order;
* workload suite — every named generator is a pure function of its seed
  (same seed = identical requests, different seed = different tokens),
  and arrivals are ticks, not wall clock;
* trainer — features rebuilt from the trace are bit-compatible with the
  serving decide() path: the constrained oracle never loses reward or
  raises rank vs the recorded actions, training is deterministic, and a
  trained checkpoint loads into ``mode="learned"`` and serves valid
  streams;
* fail-fast — drrl/learned engines without policy params refuse to
  construct.
"""
import json

import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.configs.base import RankConfig
from repro.models.api import get_model
from repro.serve import EngineConfig, Request, ServeEngine
from repro.serve.traces import TRACE_SCHEMA_VERSION, TraceReader, TraceRecorder
from repro.serve.workloads import build, make_workload, workload_names

pytestmark = pytest.mark.serve

RNG = jax.random.PRNGKey(0)
GRID = (4, 8, 12, 16)


def _cfg(mode="adaptive"):
    cfg = get_config("drrl-paper", reduced=True)
    return cfg.with_(rank=RankConfig(mode=mode, rank_grid=GRID,
                                     fixed_rank=16, segment_len=8))


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, get_model(cfg).init(RNG)


def _record_suite(cfg, params, directory, *, seed=3, n_requests=4,
                  max_new=10, shard_size=512):
    rec = TraceRecorder(directory, cfg, shard_size=shard_size,
                        scenario="suite")
    for name in workload_names():
        spec = make_workload(name, seed=seed, n_requests=n_requests,
                             max_new=max_new, vocab=cfg.vocab_size,
                             max_prompt=40)
        eng = ServeEngine(cfg, params, config=EngineConfig(
            n_slots=4, max_len=96, page_size=16, segment_len=8,
            max_new_cap=max_new, prefill_chunk=8, record_traces=rec,
            **spec.engine_overrides))
        for r in build(spec):
            eng.submit(r)
        outs = eng.run()
        assert all(0 < len(v) <= max_new for v in outs.values())
    return rec.flush()


# ---------------------------------------------------------------------------
# recorder round-trip + determinism
# ---------------------------------------------------------------------------

def test_trace_roundtrip_and_determinism(model, tmp_path):
    cfg, params = model
    m1 = _record_suite(cfg, params, tmp_path / "a")
    m2 = _record_suite(cfg, params, tmp_path / "b")
    assert m1["version"] == TRACE_SCHEMA_VERSION
    assert m1["n_records"] == m2["n_records"] > 0
    assert m1["rank_grid"] == list(GRID)

    r1, r2 = TraceReader(tmp_path / "a"), TraceReader(tmp_path / "b")
    assert len(r1) == m1["n_records"]
    assert sorted(r1.records) == sorted(r2.records)
    for col in r1.records:
        assert np.array_equal(r1.records[col], r2.records[col]), \
            f"column {col} differs between identical recording runs"
    # spectra columns carry the model geometry
    n, hkv, dh = r1.records["s2"].shape
    assert n == m1["n_records"]
    assert (hkv, dh) == (cfg.num_kv_heads, cfg.resolved_head_dim())
    # outcome windows accumulated real decode work
    assert r1.records["n_tokens"].sum() > 0
    assert (r1.records["chosen_rank"][:, None]
            == np.asarray(GRID)[None, :]).any(axis=1).all()
    # a slot's first decision has no previous segment
    assert (~r1.records["has_prev"]).any()


def test_trace_sharding_preserves_order(model, tmp_path):
    cfg, params = model
    whole = _record_suite(cfg, params, tmp_path / "one", shard_size=512)
    tiny = _record_suite(cfg, params, tmp_path / "many", shard_size=3)
    assert whole["n_records"] == tiny["n_records"]
    assert len(whole["shards"]) == 1 and len(tiny["shards"]) > 1
    a, b = TraceReader(tmp_path / "one"), TraceReader(tmp_path / "many")
    for col in a.records:
        assert np.array_equal(a.records[col], b.records[col])


def test_trace_schema_version_rejected(model, tmp_path):
    cfg, params = model
    _record_suite(cfg, params, tmp_path)
    mpath = tmp_path / "manifest.json"
    doc = json.loads(mpath.read_text())
    doc["version"] = TRACE_SCHEMA_VERSION + 1
    mpath.write_text(json.dumps(doc))
    with pytest.raises(ValueError, match="schema version"):
        TraceReader(tmp_path)
    with pytest.raises(FileNotFoundError):
        TraceReader(tmp_path / "nowhere")


def test_recorder_validates_shard_size(model, tmp_path):
    cfg, _ = model
    with pytest.raises(ValueError, match="shard_size"):
        TraceRecorder(tmp_path, cfg, shard_size=0)


# ---------------------------------------------------------------------------
# workload suite determinism
# ---------------------------------------------------------------------------

def test_workloads_seed_reproducible():
    for name in workload_names():
        a = make_workload(name, seed=5, n_requests=6)
        b = make_workload(name, seed=5, n_requests=6)
        c = make_workload(name, seed=6, n_requests=6)
        assert a.engine_overrides == b.engine_overrides
        assert len(a.requests) == 6
        for ra, rb in zip(a.requests, b.requests):
            assert ra.keys() == rb.keys()
            assert np.array_equal(ra["tokens"], rb["tokens"])
            assert ra["arrival"] == rb["arrival"]
        assert any(not np.array_equal(ra["tokens"], rc["tokens"])
                   for ra, rc in zip(a.requests, c.requests)), \
            f"{name}: different seeds produced identical token streams"
        for req in build(a):
            assert isinstance(req.arrival, int)  # ticks, never wall clock


def test_workload_unknown_name():
    with pytest.raises(ValueError, match="unknown workload"):
        make_workload("nope")


def test_workload_shapes():
    spec = make_workload("shared_prefix", seed=1, n_requests=5)
    assert spec.engine_overrides == {"prefix_cache": True}
    toks = [r["tokens"] for r in spec.requests]
    # chat turns share one of the few system prefixes
    assert any(np.array_equal(toks[i][:8], toks[j][:8])
               for i in range(5) for j in range(i + 1, 5))
    mixed = make_workload("mixed_sampling", seed=1, n_requests=6)
    assert mixed.engine_overrides == {"sampling": True, "nucleus": True}
    kinds = [("top_k" in r, "top_p" in r) for r in mixed.requests]
    assert (True, False) in kinds and (False, True) in kinds


# ---------------------------------------------------------------------------
# offline trainer + mode="learned" round trip
# ---------------------------------------------------------------------------

def test_train_and_serve_learned(model, tmp_path):
    from repro.train.serve_policy import (build_dataset, evaluate_policy,
                                          load_policy, train_serve_policy)
    cfg, params = model
    _record_suite(cfg, params, tmp_path / "trace")
    ds = build_dataset(tmp_path / "trace", cfg.rank)
    assert ds["feats"]["ner"].shape == (ds["n"] * ds["h"], len(GRID))

    # the constrained oracle dominates the recorded heuristic: per
    # record, reward can only go up and kept rank can only go down
    idx = np.arange(ds["n"])
    rew = np.asarray(ds["reward_matrix"])
    assert (rew[idx, np.asarray(ds["oracle"])]
            >= rew[idx, np.asarray(ds["actions"])] - 1e-6).all()
    assert (np.asarray(ds["grid"])[np.asarray(ds["oracle"])]
            <= np.asarray(ds["grid"])[np.asarray(ds["actions"])]).all()

    pol, hist = train_serve_policy(
        tmp_path / "trace", cfg.rank, out_dir=tmp_path / "pol",
        bc_steps=30, ppo_steps=2, ppo_epochs=1)
    ev = hist["eval"]
    assert ev["learned"]["reward"] >= ev["adaptive"]["reward"] - 2e-3
    assert (ev["learned"]["mean_rank"]
            <= ev["adaptive"]["mean_rank"] * 1.005)

    # checkpoint round trip: loaded tree serves in mode="learned"
    pol2 = load_policy(tmp_path / "pol")
    for a, b in zip(jax.tree_util.tree_leaves(pol),
                    jax.tree_util.tree_leaves(pol2)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    lcfg = _cfg("learned")
    eng = ServeEngine(lcfg, params, pol2, config=EngineConfig(
        n_slots=2, max_len=64, page_size=16, segment_len=8, max_new_cap=8,
        prefill_chunk=8))
    rnd = np.random.default_rng(0)
    for i in range(2):
        eng.submit(Request(rid=i, tokens=rnd.integers(
            1, cfg.vocab_size, 12).astype(np.int32), max_new=8))
    outs = eng.run()
    assert all(len(v) == 8 for v in outs.values())
    # offline greedy mirror agrees with itself across calls (pure fn)
    e1 = evaluate_policy(ds, cfg.rank, policy_params=pol2)
    e2 = evaluate_policy(ds, cfg.rank, policy_params=pol2)
    assert e1 == e2


def test_train_rejects_empty_trace(model, tmp_path):
    from repro.train.serve_policy import build_dataset
    cfg, _ = model
    TraceRecorder(tmp_path, cfg).flush()        # no records
    with pytest.raises(ValueError, match="empty"):
        build_dataset(tmp_path, cfg.rank)


def test_load_policy_missing_meta(tmp_path):
    from repro.train.serve_policy import load_policy
    with pytest.raises(FileNotFoundError, match="policy_meta"):
        load_policy(tmp_path)


# ---------------------------------------------------------------------------
# fail-fast: policy modes refuse to serve without params
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["drrl", "learned"])
def test_policy_mode_requires_params(model, mode):
    _, params = model
    with pytest.raises(ValueError, match="needs policy params"):
        ServeEngine(_cfg(mode), params, config=EngineConfig(
            n_slots=2, max_len=64, page_size=16, segment_len=8))
