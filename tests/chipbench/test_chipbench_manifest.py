"""BENCHMARK.json against the benchmark's rules, and discovery by name."""
import json
import shutil
from pathlib import Path

import pytest

from chipbench import manifest, model

ROOT = manifest.PKG_ROOT
DATA = Path(__file__).parent / "data"
# keys that name a width, which no cut may change
WIDTH_WORDS = ("hidden_size", "intermediate", "head_dim", "_dim", "_rank",
               "latent", "state", "proj", "expand", "per_tok")


@pytest.fixture(scope="module")
def doc():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_manifest_follows_the_rules(doc):
    assert manifest.problems(doc) == []


@pytest.mark.parametrize("name,ok", [
    ("drrl-paper.chat", True), ("_x", True), ("a" * 64, True),
    ("a" * 65, False), ("has space", False), ("a/b", False), ("a,b", False),
    ("-lead", False), ("μs", False)])
def test_name_characters(name, ok):
    assert bool(manifest.NAME_RE.match(name)) is ok


@pytest.mark.parametrize("unit,ok", [
    ("tokens/s", True), ("%", True), ("ms", True), ("x" * 16, True),
    ("x" * 17, False), ("tokens per s", False), ("µs", False), ("", False)])
def test_unit_characters(unit, ok):
    assert bool(manifest.UNIT_RE.match(unit)) is ok


def test_every_per_layer_metric_moves_a_metric_its_cells_report(doc):
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    cells = [w["name"] for w in doc["workloads"]]
    for m in doc["per_layer"]:
        for c in m.get("workloads", cells):
            assert c in e2e[m["moves"]].get("workloads", cells), (m, c)


def test_a_broken_manifest_is_caught(doc):
    bad = json.loads(json.dumps(doc))
    bad["end_to_end"].append({"name": "elsewhere_s", "unit": "s",
                              "better": "lower", "bound": 0.1,
                              "source": "host_clock", "workloads": []})
    bad["per_layer"][0]["moves"] = "elsewhere_s"
    bad["end_to_end"][1]["unit"] = "per second"
    probs = manifest.problems(bad)
    assert any("does not report" in p for p in probs)
    assert any("bad unit" in p for p in probs)


def test_every_named_file_is_found(doc):
    bench = manifest.Bench.load()
    for w in doc["workloads"]:
        cell = bench.cell(w["name"])
        assert bench.config_path(cell.config).is_file()
        assert bench.mix_path(cell.traffic).is_file()
        for m in cell.per_layer:
            assert callable(bench.reader(m["name"]))
    for c in doc["configs"]:
        assert (ROOT / c["file"]).is_file()


def test_config_files_match_the_manifest(doc):
    for c in doc["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert key in cfg["model"]
            assert not any(w in key for w in WIDTH_WORDS), key
        spec = model.load_spec(ROOT / c["file"])
        assert spec.max_len >= 2 and spec.gap_limit > 0


def test_a_cell_from_data_alone(tmp_path, doc):
    """A new cell that reuses a configuration, plus a new mix: data only."""
    new = json.loads(json.dumps(doc))
    new["workloads"].append({"name": "drrl-paper.short", "config":
                             "drrl-paper", "traffic": "test-short",
                             "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    mixes = tmp_path / "chipbench" / "mixes"
    mixes.mkdir(parents=True)
    shutil.copy(ROOT / "chipbench" / "mixes" / "chat.json",
                mixes / "test-short.json")
    bench = manifest.Bench.load(tmp_path)
    cell = bench.cell("drrl-paper.short")
    assert bench.mix_path(cell.traffic) == mixes / "test-short.json"
    assert bench.config_path(cell.config) == (
        ROOT / "chipbench" / "configs" / "drrl-paper.json")
    names = [m["name"] for m in cell.end_to_end]
    assert names == ["setup_s"]                 # only unscoped metrics
    with pytest.raises(KeyError):
        bench.cell("nope.cell")


def test_test_data_discovers_its_own_files():
    bench = manifest.Bench.load(DATA)
    cell = bench.cell("tiny-gqa.sessions")
    assert bench.mix_path(cell.traffic).parent == DATA / "chipbench" / "mixes"
    assert callable(bench.reader("idle_share.sessions"))    # split name
    with pytest.raises(FileNotFoundError):
        bench.reader("no_such_metric")
    assert manifest.problems(bench.doc) == []
