"""The long-context cell's pieces on the CPU: its readers on a synthetic
run record, and whole runs of a test-only cell that keeps the
`internlm2-20b.longctx` ratios at a test's size (its own manifest under
``longctx_data``)."""
from pathlib import Path

import pytest

from chipbench import manifest, model, run
from chipbench.trace import Trace

DATA = Path(__file__).parent / "longctx_data"
CONF = manifest.PKG_ROOT / "chipbench" / "configs"
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SEED = 2**31 + 5


def read(name, rec):
    return manifest.Bench.load().reader(name)(rec)


@pytest.fixture()
def rec():
    spec = model.load_spec(CONF / "drrl-paper.json")
    tr = Trace(calls={"jit__step_impl": [(2.0, 0.03), (5.0, 0.03)],
                      "jit__step_mixed_impl": [(1.0, 0.05)],
                      "jit_decide": [(3.0, 0.01), (4.0, 0.02)]},
               busy_s=4.0, window_s=5.0, n_devices=1)
    return run.Record("x", spec, V5E, 10.0, 0.0, 10.0, [],
                      {"steps": 10, "mixed_steps": 2},
                      {"steps": 110, "mixed_steps": 27}, [], tr)


def test_decode_step_reader_reads_the_pure_decode_step(rec):
    assert read("decode_step_ms", rec) == pytest.approx(30.0)
    assert read("idle_share.longctx", rec) == pytest.approx(20.0)
    rec.trace.calls.pop("jit__step_impl")
    assert read("decode_step_ms", rec) is None


def test_rank_counter_readers(rec):
    # 8 decisions in the window chose 40 + 7 x 64 of r_keep 64; 2 vetoed
    rec.counters0.update(decides=3, rank_chosen_sum=150, rank_vetoes=1)
    rec.counters1.update(decides=11, rank_chosen_sum=150 + 40 + 7 * 64,
                         rank_vetoes=3)
    assert rec.spec.r_keep == 64
    assert read("rank_kept_share", rec) == pytest.approx(
        100.0 * (40 + 7 * 64) / (8 * 64))
    assert read("rank_veto_share", rec) == pytest.approx(25.0)
    rec.counters1["decides"] = 3            # no decision in the window
    assert read("rank_kept_share", rec) is None
    assert read("rank_veto_share", rec) is None


def test_rank_readers_stay_silent_on_a_program_without_the_counters(rec):
    rec.counters0["decides"], rec.counters1["decides"] = 3, 11
    for name in ("rank_kept_share", "rank_veto_share"):
        assert read(name, rec) is None


def test_longctx_manifest_follows_the_rules():
    bench = manifest.Bench.load(DATA)
    assert manifest.problems(bench.doc) == []


def run_tiny(cell, trace=False, **kw):
    return run.run_cell(cell, SEED, 2.0, trace, root=DATA,
                        require_tpu=False, **kw)


# The long-context cell's ratios at a test's size: bfloat16 program,
# GQA 6:1, the factor pool on (r_keep 8 of head_dim 16), prompts of 3-5
# segments and 24 new tokens, so each stream decides at decode steps 0,
# 8 and 16. Limit 0.03 (tiny-bf16.json): the program read 0-0.008 over
# five seeds, all roundings it shares with a bfloat16 deployment; the
# float8 control read 0.074-0.14, a token the reference ranks that far
# below its best being what a lower precision picks.
@pytest.fixture(scope="module")
def longctx():
    return run_tiny("tiny-bf16.longctx", trace=True,
                    controls=("float8_e4m3fn",))


def test_longctx_program_is_correct_and_counts_its_decisions(longctx):
    info, chk = longctx["info"], longctx["checks"]
    assert chk["bad_rank_records"]["value"] == 0
    assert chk["short_outputs"]["value"] == 0
    assert chk["tokens_checked"]["value"] == 3 * 24
    assert info["program_gap"] <= chk["logit_gap"]["limit"]
    assert info["compiles_in_window"] == 0
    m = longctx["metrics"]
    assert 0.0 < m["rank_kept_share"]["value"] <= 100.0
    assert 0.0 <= m["rank_veto_share"]["value"] <= 100.0
    # the CPU has no device plane: the device readers stay silent
    assert "decode_step_ms" not in m and "idle_share.longctx" not in m


def test_longctx_control_fails_the_limit(longctx):
    chk, info = longctx["checks"]["logit_gap"], longctx["info"]
    assert longctx["correct"] is False
    assert chk["value"] == info["control_gaps"]["float8_e4m3fn"]
    assert chk["value"] > chk["limit"] >= info["program_gap"]


def test_longctx_untraced_run_reports_tpot():
    res = run_tiny("tiny-bf16.longctx")
    assert res["correct"] is True
    assert set(res["metrics"]) == {"setup_s", "tpot_p50_ms"}
    assert res["attempted"] == 3
