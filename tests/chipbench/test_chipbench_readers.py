"""Per-layer readers on a synthetic run record: each reads what its
docstring says, and stays silent when its source is missing."""
import numpy as np
import pytest

from chipbench import manifest, model, run, traffic
from chipbench.trace import Trace

CONF = manifest.PKG_ROOT / "chipbench" / "configs"
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def req(prompt_len, times, due=0.0, submit=0.0, done=None):
    r = run.Req(traffic.Item(due, np.zeros(prompt_len, np.int32), 100),
                due, in_window=True, rid=prompt_len, submit=submit)
    r.times = list(times)
    r.done = done
    return r


@pytest.fixture()
def rec():
    spec = model.load_spec(CONF / "drrl-paper.json")
    reqs = [req(4096, np.arange(0.0, 10.0, 0.04), done=False),
            req(8192, np.arange(0.0, 10.0, 0.04), due=-1.0, submit=-0.99,
                done=False)]
    tr = Trace(calls={"jit__step_impl": [(2.0, 0.03), (5.0, 0.03)],
                      "jit__step_mixed_impl": [(1.0, 0.05)],
                      "jit_decide": [(3.0, 0.01), (4.0, 0.02)]},
               busy_s=4.0, window_s=5.0, n_devices=1)
    spans = [{"name": "request", "ph": "b", "id": "4096", "abs_s": 0.5},
             {"name": "request", "ph": "b", "id": "8192", "abs_s": 0.0}]
    return run.Record("x", spec, V5E, 10.0, 0.0, 10.0, reqs,
                      {"steps": 10, "mixed_steps": 2},
                      {"steps": 110, "mixed_steps": 27}, spans, tr)


def read(name, rec):
    return manifest.Bench.load().reader(name)(rec)


def test_device_time_readers(rec):
    assert read("mixed_step_ms", rec) == pytest.approx(50.0)
    assert read("idle_share.chat", rec) == pytest.approx(20.0)


def test_counter_and_span_readers(rec):
    assert read("mixed_step_share", rec) == pytest.approx(25.0)
    assert read("queue_wait_p95_ms", rec) == pytest.approx(
        np.percentile([500.0, 1000.0], 95))
    assert read("gen_lag_p95_ms", rec) == pytest.approx(
        np.percentile([0.0, 10.0], 95))


def test_readers_stay_silent_without_their_source(rec):
    rec.trace, rec.peaks = None, None
    for name in ("mixed_step_ms", "idle_share.chat"):
        assert read(name, rec) is None
