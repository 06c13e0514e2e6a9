"""Whole runs of test-only cells on the CPU: the harness with its look
for a chip skipped, at a size a test can hold. A sound run is correct;
the control (the reference in float8 in the program's place) comes out
not correct through the same comparison."""
from pathlib import Path

import pytest

from chipbench import run

DATA = Path(__file__).parent / "data"
SEED = 2**31 + 5


def run_tiny(cell, trace=False, **kw):
    return run.run_cell(cell, SEED, 2.0, trace, root=DATA,
                        require_tpu=False, **kw)


@pytest.fixture(scope="module")
def sound():
    return run_tiny("tiny-gqa.chat")


def test_sound_run_is_correct(sound):
    assert sound["correct"] is True
    assert sound["attempted"] >= 3 and sound["failed"] == 0
    chk = sound["checks"]
    assert list(sound)[-1] == "checks"
    assert chk["tokens_checked"]["value"] >= 20
    assert chk["logit_gap"]["value"] <= chk["logit_gap"]["limit"]
    assert set(sound["metrics"]) == {"setup_s", "ttft_p50_s"}
    assert sound["info"]["compiles_in_window"] == 0
    for k in ("platform", "kind", "count", "memory_peak_bytes"):
        assert k in sound["device"]


def test_the_control_fails_the_limit():
    res = run_tiny("tiny-gqa.chat", controls=("float8_e4m3fn", "bfloat16"))
    chk, info = res["checks"]["logit_gap"], res["info"]
    assert res["correct"] is False
    assert chk["value"] == info["control_gaps"]["float8_e4m3fn"]
    assert chk["value"] > chk["limit"] >= info["program_gap"]


def test_closed_loop_traced_run():
    res = run_tiny("tiny-gqa.sessions", trace=True)
    assert res["correct"] is True
    assert res["attempted"] == 6            # 3 sessions x 2 turns
    assert "busy_s" in res["device"] and "window_s" in res["device"]
    # the CPU has no device plane: the device readers stay silent
    assert "idle_share.sessions" not in res["metrics"]
