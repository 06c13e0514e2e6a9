"""The trace reduction, on synthetic intervals and on a small trace of a
test-only engine recorded on a TPU v5e (``fixtures/``)."""
import gzip
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from chipbench import trace as tr

FIX = Path(__file__).parent / "fixtures"


def test_union_length_merges_overlaps():
    iv = np.array([[0.0, 1.0], [0.5, 2.0], [3.0, 4.0], [3.5, 3.6]])
    total, merged = tr.union_length(iv)
    assert total == pytest.approx(3.0)
    assert merged.tolist() == [[0.0, 2.0], [3.0, 4.0]]
    assert tr.union_length(np.zeros((0, 2)))[0] == 0.0


def test_self_time_leaves_out_nested_ops():
    ev = [(0.0, 10.0, "while"), (1.0, 3.0, "fusion"), (4.0, 5.0, "copy"),
          (6.0, 7.0, "fusion"), (12.0, 13.0, "copy")]
    st = tr.self_times(ev)
    assert st["while"] == pytest.approx(10.0 - 2.0 - 1.0 - 1.0)
    assert st["fusion"] == pytest.approx(3.0)
    assert st["copy"] == pytest.approx(2.0)
    assert sum(st.values()) == pytest.approx(11.0)     # = the busy union


def test_labels():
    assert tr.module_name("jit__step_impl(123456)") == "jit__step_impl"
    assert tr.op_label("%fusion.3 = f32[8,128]{1,0:T(8,128)} fusion(f32[8] "
                       "%p), kind=kLoop") == "fusion.3 f32[8,128] fusion"
    assert tr.op_label("%while.5 = (s32[], f32[4]{0}) while((s32[], "
                       "f32[4]) %t)") == "while.5 (tuple) while"
    assert tr.label_gap(1.5, [("dispatch", 1.0, 2.0)]) == "host: dispatch"
    assert tr.label_gap(3.0, [("dispatch", 1.0, 2.0)]).startswith("host: ")


@pytest.fixture(scope="module")
def fixture_trace(tmp_path_factory):
    meta = json.loads((FIX / "tiny.meta.json").read_text())
    path = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    with gzip.open(FIX / "tiny.xplane.pb.gz") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    t = tr.reduce_trace(str(path), meta["anchor"],
                        tuple(meta["window"]),
                        [tuple(p) for p in meta["phases"]])
    return t, meta


def test_recorded_trace_reduces(fixture_trace):
    t, meta = fixture_trace
    assert t.n_devices == 1
    assert t.window_s == pytest.approx(meta["window"][1] - meta["window"][0])
    assert 0.0 < t.busy_s < t.window_s
    mods = set(t.calls)
    assert {"jit__step_mixed_impl", "jit_decide"} & mods
    w0, w1 = meta["window"]
    for name, runs in t.calls.items():
        assert "(" not in name
        for s, d in runs:
            assert w0 <= s < w1 and d >= 0
    assert t.calls_of("_step_impl") == sorted(t.calls.get("jit__step_impl",
                                                          []))
    assert len(t.top_ops) <= 10 and len(t.idle_gaps) <= 10
    secs = [s for _, s in t.top_ops]
    assert secs == sorted(secs, reverse=True)
    assert sum(secs) <= t.busy_s * 1.001
    gaps = [g for _, g in t.idle_gaps]
    assert all(lbl.startswith("host: ") for lbl, _ in t.idle_gaps)
    assert sum(gaps) <= t.window_s - t.busy_s + 1e-6


def test_a_run_without_a_trace_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        tr.find_xplane(str(tmp_path))
