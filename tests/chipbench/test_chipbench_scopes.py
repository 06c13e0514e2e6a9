"""Device time by the program's named scopes and host step phases from
the profiler's own annotations (``chipbench/scopes.py``): on the CPU with
a compiled test-only engine and synthetic events, and on a small trace of
that engine recorded on a TPU v5e (``fixtures/scoped.*``)."""
import gzip
import json
import logging
import re
import shutil
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from chipbench import model, run, scopes
from chipbench import trace as tr
from chipbench.metrics import host_step_ms

DATA = Path(__file__).parent / "data"
FIX = Path(__file__).parent / "fixtures"
SEED = 2**31 + 11
STEP_SCOPES = ("embed", "layers", "qkv", "kv_write", "kv_read",
               "rank_proj", "attend", "mass", "out_proj", "ffn", "head",
               "sample", scopes.POOL_IO)
DECIDE_SCOPES = ("gather_k", "gram", "eigh", "reproject")
BOOKKEEPING = {"parameter", "get-tuple-element", "tuple", "constant"}
GROUPED = {s for members in scopes.GROUPS.values() for s in members}
_COMP = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+) .*\{$")
_NAME = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ")


def computations(text):
    """Computation name -> the names of its instructions."""
    out, cur = {}, None
    for line in text.splitlines():
        m = _COMP.match(line.rstrip())
        if m:
            cur = out.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None and _NAME.match(line):
            cur.append(_NAME.match(line).group(1))
    return out


@pytest.fixture(scope="module")
def tiny_engine():
    from repro.core.drrl import init_agent
    from repro.serve.api import Engine, EngineConfig
    spec = model.load_spec(DATA / "chipbench" / "configs" / "tiny-gqa.json")
    cfg = run.model_config(spec)
    eng = Engine(cfg, model.init_params(spec, SEED),
                 init_agent(jax.random.PRNGKey(1), cfg.rank, cfg.d_model),
                 config=EngineConfig(n_slots=spec.n_slots,
                                     max_len=spec.max_len,
                                     max_new_cap=spec.max_new_cap,
                                     obs_trace=True))
    eng.warmup()
    return eng


@pytest.fixture(scope="module")
def texts(tiny_engine):
    counter = run.CompileCount()
    log = logging.getLogger("jax")
    jax.config.update("jax_log_compiles", True)
    log.addHandler(counter)
    try:
        out = tiny_engine.compiled_text()
    finally:
        log.removeHandler(counter)
        jax.config.update("jax_log_compiles", False)
    return out, counter.count


def test_compiled_text_names_every_scope_and_compiles_nothing(texts):
    out, compiles = texts
    assert compiles == 0
    assert set(out) == {"jit__step_impl", "jit__step_mixed_impl",
                        "jit_decide"}
    for mod, want in (("jit__step_impl", STEP_SCOPES),
                      ("jit__step_mixed_impl", STEP_SCOPES),
                      ("jit_decide", DECIDE_SCOPES)):
        named = {scopes.scope_of(o)
                 for _, _, _, o in scopes.instructions(out[mod]) if o}
        assert set(want) <= named, (mod, set(want) - named)


@pytest.mark.parametrize("mod", ["jit__step_impl", "jit__step_mixed_impl"])
def test_the_layer_scan_body_lands_in_groups(texts, mod):
    text = texts[0][mod]
    smap = scopes.scope_map(text)
    ops = {n: op for n, op, _, _ in scopes.instructions(text)}
    bodies = [re.search(r"body=%([\w.\-]+)", line).group(1)
              for line in text.splitlines()
              if " while(" in line and _NAME.match(line)
              and smap[_NAME.match(line).group(1)] == "layers"]
    assert len(bodies) == 1
    body = [n for n in computations(text)[bodies[0]]
            if ops[n] not in BOOKKEEPING]
    grouped = [n for n in body if smap[n] in GROUPED]
    assert len(body) > 20 and len(grouped) >= 0.9 * len(body)


HLO = """HloModule jit__step_mixed_impl, entry_computation_layout={()->f32[8]}

ENTRY %main.9 (p.1: f32[2,8], i.1: s32[]) -> f32[8] {
  %p.1 = f32[2,8]{1,0} parameter(0)
  %i.1 = s32[] parameter(1)
  %ds.1 = f32[1,8]{1,0} dynamic-slice(f32[2,8]{1,0} %p.1, s32[] %i.1), \
metadata={op_name="jit(_step_mixed_impl)/layers/while/body/dynamic_slice"}
  %copy.1 = f32[1,8]{0,1:T(8,128)} copy(f32[1,8]{1,0} %ds.1)
  %fusion.1 = f32[8]{0} fusion(f32[1,8]{0,1} %copy.1), kind=kLoop, \
calls=%f.1, metadata={op_name="jit(_step_mixed_impl)/layers/while/body/\
closed_call/kv_read/gather"}
  %fusion.2 = f32[8]{0} fusion(f32[8]{0} %fusion.1, f32[2,8]{1,0} %p.1), \
kind=kLoop, calls=%f.2, metadata={op_name="jit(_step_mixed_impl)/layers/\
while/body/closed_call/attend/jit(_where)/select_n"}
  %fusion.3 = (f32[8]{0}, s32[]) fusion(f32[8]{0} %fusion.2), \
kind=kLoop, calls=%f.3, metadata={op_name="jit(_step_mixed_impl)/layers/\
while/body/closed_call/ffn/mul"}
  %gte.1 = f32[8]{0} get-tuple-element((f32[8]{0}, s32[]) %fusion.3), index=0
  %other.1 = f32[8]{0} add(f32[8]{0} %gte.1, f32[8]{0} %fusion.2)
  %copy.2 = f32[8]{0} copy(f32[8]{0} %other.1)
  %dus.1 = f32[2,8]{1,0} dynamic-update-slice(f32[2,8]{1,0} %p.1, \
f32[8]{0} %copy.2, s32[] %i.1), metadata={op_name="jit(_step_mixed_impl)/\
layers/while/body/dynamic_update_slice"}
  %tuple.1 = (f32[2,8]{1,0}) tuple(f32[2,8]{1,0} %dus.1)
  %while.1 = (f32[2,8]{1,0}) while((f32[2,8]{1,0}) %tuple.1), body=%b.1, \
metadata={op_name="jit(_step_mixed_impl)/layers/while"}
  %gte.2 = f32[2,8]{1,0} get-tuple-element((f32[2,8]{1,0}) %while.1), index=0
  ROOT %copy.3 = f32[2,8]{0,1} copy(f32[2,8]{1,0} %gte.2)
}
"""


def test_scope_map_reads_metadata_and_one_hop():
    smap = scopes.scope_map(HLO)
    assert smap["ds.1"] == smap["dus.1"] == scopes.POOL_IO
    assert smap["copy.1"] == scopes.POOL_IO          # its one operand
    assert smap["copy.2"] == scopes.POOL_IO          # its one user
    assert smap["fusion.1"] == "kv_read" and smap["fusion.3"] == "ffn"
    assert smap["fusion.2"] == "attend"
    assert smap["other.1"] is None                   # two operands
    assert smap["while.1"] == "layers"
    assert smap["copy.3"] == scopes.POOL_IO          # the scan's pools out
    assert scopes.scope_of("jit(f)/sample/vmap(jit(_gumbel))/while") \
        == "sample"
    assert scopes.scope_of("jit(f)/and") is None


def _ev(name, s, e):
    return (float(s), float(e - s), f"%{name} = f32[8]{{0}} fusion()")


def test_exclusive_times_count_partial_overlaps_once():
    # b starts inside a, c inside b and past a's end: a TPU op line
    ev = [(0.0, 10.0, "a"), (2.0, 5.0, "b"), (4.0, 12.0, "c"),
          (13.0, 14.0, "d")]
    ex = scopes.exclusive_times(ev)
    assert ex == pytest.approx({"a": 2.0 + 0.0, "b": 2.0, "c": 8.0,
                                "d": 1.0})
    assert sum(ex.values()) == pytest.approx(13.0)     # the union
    nested = [(0.0, 10.0, "while"), (1.0, 3.0, "fusion"), (4.0, 5.0, "copy")]
    assert scopes.exclusive_times(nested) == pytest.approx(
        tr.self_times(nested))


def test_group_sums_and_unscoped_make_the_union():
    # one run in the window, ops nested in a while; an earlier run that
    # started before the window and its op do not count
    mods = [(-5.0, 4.0, "jit__step_mixed_impl"),
            (0.0, 10.5, "jit__step_mixed_impl")]
    ops = [_ev("fusion.1", -4.5, -4.0), _ev("while.1", 0, 10),
           _ev("ds.1", 0, 1), _ev("copy.1", 1, 3), _ev("fusion.1", 3, 5),
           _ev("fusion.2", 5, 6), _ev("fusion.3", 6, 8),
           _ev("other.1", 8, 9)]
    times = scopes.scope_times(
        [(mods, ops)], {"jit__step_mixed_impl": scopes.scope_map(HLO)},
        (-1.0, 20.0))
    e = times["jit__step_mixed_impl"]
    assert e["runs"] == 1
    g = scopes.group_ms(e)
    assert g["pool_io"] == pytest.approx(3e3)
    assert g["kv"] == pytest.approx(2e3)
    assert g["attn"] == pytest.approx(1e3)
    assert g["dense"] == pytest.approx(2e3)
    assert g["unscoped_share"] == pytest.approx(20.0)   # other.1, while
    union, _ = tr.union_length(np.array([(s, s + d) for s, d, _ in ops[1:]]))
    grouped = sum(g[k] for k in scopes.GROUPS)
    assert grouped + g["unscoped_share"] * g["step_ms"] / 100 \
        == pytest.approx(union * 1e3)
    assert g["step_ms"] == pytest.approx(union * 1e3)
    assert {op for op, _ in scopes.unscoped_ops(e)} == {"other.1", "while.1"}


def test_phase_annotations_match_the_spans(tiny_engine, tmp_path):
    from repro.serve.api import SamplingParams
    eng = tiny_engine
    eng.obs.reset_run()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(tr.ANCHOR):
            anchor = time.perf_counter()
        for n in (9, 14):
            eng.submit(list(range(1, n + 1)), SamplingParams(max_new=5))
        eng.run()
    finally:
        jax.profiler.stop_trace()
    t = eng.obs.tracer
    spans = [(e["name"], t.origin_s + e["ts"] * 1e-6,
              t.origin_s + (e["ts"] + e["dur"]) * 1e-6)
             for e in t.events if e.get("cat") == "phase"]
    ann = scopes.phase_spans(tr.find_xplane(str(tmp_path)), anchor)
    assert len(spans) > 10
    assert [p for p, _, _ in ann] == [p for p, _, _ in spans]
    for (_, s0, e0), (_, s1, e1) in zip(ann, spans):
        assert abs(s0 - s1) < 1e-3 and abs(e0 - e1) < 1e-3


def test_host_step_reader_reads_the_phase_counters():
    keys = {k: 0.0 for k in host_step_ms.KEYS}
    old = SimpleNamespace(counters0={"steps": 0}, counters1={"steps": 4})
    assert host_step_ms.read(old) is None           # a program without them
    rec = SimpleNamespace(
        counters0={"steps": 10, "phase.fetch_s": 1.0, **keys},
        counters1={"steps": 14, "phase.fetch_s": 9.0,
                   **{k: 0.002 for k in host_step_ms.KEYS}})
    assert host_step_ms.read(rec) == pytest.approx(1e3 * 0.010 / 4)


@pytest.mark.parametrize("trace", [True, False], ids=["traced", "untraced"])
def test_scoped_run_counts_host_time_alike(trace):
    res = scopes.run_scoped("tiny-gqa.chat", SEED, 2.0, trace, True,
                            root=DATA, require_tpu=False)
    assert res["correct"] is True
    h = res["scoped"]["host_step_ms"]
    assert h["counters"] > 0
    assert h["spans"] == pytest.approx(h["counters"], rel=0.1)
    assert run.Record.__name__ == "Record"          # the hooks are undone


# -- the recorded trace ------------------------------------------------

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("scoped") / "scoped.xplane.pb"
    with gzip.open(FIX / "scoped.xplane.pb.gz") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    meta = json.loads((FIX / "scoped.meta.json").read_text())
    with gzip.open(FIX / "scoped.hlo.json.gz", "rt") as f:
        hlo = json.load(f)
    return str(path), meta, hlo


def test_recorded_gaps_label_alike_from_annotations_and_spans(recorded):
    path, meta, _ = recorded
    g = scopes.gap_labels(path, meta["anchor"], tuple(meta["window"]),
                          [tuple(p) for p in meta["phases"]])
    assert g["of"] == 10 and g["agree"] >= 9
    assert all(lbl.startswith("host: ") for lbl, _ in g["annotations"])


def test_recorded_mixed_step_is_attributed(recorded):
    path, meta, hlo = recorded
    maps = {m: scopes.scope_map(t) for m, t in hlo.items()}
    times = scopes.scope_times(scopes.device_events(path, meta["anchor"]),
                               maps, tuple(meta["window"]))
    e = times[scopes.MIXED]
    assert e["runs"] >= 1
    assert all(op in maps[scopes.MIXED] for op in e["ops"])
    g = scopes.group_ms(e)
    assert all(g[k] > 0 for k in scopes.GROUPS)
    grouped = sum(g[k] for k in scopes.GROUPS)
    assert grouped + g["unscoped_share"] * g["step_ms"] / 100 \
        == pytest.approx(g["step_ms"])
    assert g["unscoped_share"] <= 10.0
