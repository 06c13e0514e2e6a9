"""The traffic generator: deterministic per seed, same work for every
seed, and the stated length distributions."""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import manifest, traffic

MIX = manifest.PKG_ROOT / "chipbench" / "mixes"
TEST_MIX = Path(__file__).parent / "data" / "chipbench" / "mixes"


def gen(name, seed, seconds=51.0, **kw):
    path = MIX / f"{name}.json"
    mix = traffic.load_mix(path if path.exists()
                           else TEST_MIX / f"{name}.json")
    kw.setdefault("max_len", 2048)
    kw.setdefault("max_new_cap", 512)
    return mix, traffic.generate(mix, seed, seconds, 50257, **kw)


def lengths(s):
    return ([len(i.prompt) for i in s.items], [i.max_new for i in s.items])


@pytest.mark.parametrize("name", ["chat", "tiny-sessions"])
def test_same_seed_same_schedule(name):
    _, a = gen(name, 2**31 + 7)
    _, b = gen(name, 2**31 + 7)
    assert [i.due_s for i in a.items] == [i.due_s for i in b.items]
    for x, y in zip(a.items, b.items):
        assert np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new


@pytest.mark.parametrize("name", ["chat", "tiny-sessions"])
def test_seeds_share_the_work_in_another_order(name):
    _, a = gen(name, 11)
    _, b = gen(name, 12)

    def window(s):
        return [i for i in s.items if 0.0 <= i.due_s < 51.0]
    pa, oa = lengths(traffic.Schedule(a.loop, window(a)))
    pb, ob = lengths(traffic.Schedule(b.loop, window(b)))
    assert sorted(pa) == sorted(pb) and sorted(oa) == sorted(ob)
    assert pa != pb
    assert not np.array_equal(a.items[0].prompt[:8], b.items[0].prompt[:8])
    if a.loop == "open":
        ga = np.sort(np.diff([i.due_s for i in window(a)]))
        gb = np.sort(np.diff([i.due_s for i in window(b)]))
        assert not np.allclose(np.diff([i.due_s for i in window(a)]),
                               np.diff([i.due_s for i in window(b)]))
        assert len(ga) == len(gb)


def test_chat_lengths_follow_the_stated_lognormals():
    mix, s = gen("chat", 5, seconds=400.0)
    p, o = map(np.asarray, lengths(s))
    assert p.min() >= 64 and p.max() <= 1536
    assert o.min() >= 16 and o.max() <= 512
    assert abs(np.median(p) - 512) <= 16
    assert abs(np.median(o) - 128) <= 4
    sig = np.std(np.log(p[(p > 64) & (p < 1536)] / 512))
    assert 0.45 < sig < 0.65


def test_chat_arrivals_are_poisson_at_the_rate():
    mix, s = gen("chat", 9, seconds=51.0)
    due = np.asarray([i.due_s for i in s.items])
    rate = mix["rate_per_s"]
    assert due[0] < -mix["ramp_s"] + 5 / rate
    win = due[(due >= 0) & (due < 51.0)]
    assert len(win) == round(rate * 51.0)
    assert win[0] == 0.0
    gaps = np.diff(win)
    assert abs(np.mean(gaps) - 1 / rate) < 0.15 / rate
    assert abs(np.std(gaps) / np.mean(gaps) - 1.0) < 0.3    # exponential


def test_closed_loop_sessions_take_turns():
    mix, s = gen("tiny-sessions", 3)
    assert s.loop == "closed" and s.sessions == 3 and len(s.items) == 6
    for it in s.items:
        assert 16 <= len(it.prompt) <= 48 and it.max_new == 12
    assert [(i.session, i.turn) for i in s.items] == [
        (0, 0), (1, 0), (2, 0), (0, 1), (1, 1), (2, 1)]


def test_a_mix_is_data_for_the_one_generator(tmp_path):
    mix = {"loop": "closed", "sessions": 2, "turns": 3,
           "prompt_len": {"dist": "fixed", "value": 10},
           "max_new": {"dist": "uniform", "min": 3, "max": 5}}
    path = tmp_path / "m.json"
    path.write_text(json.dumps(mix))
    s = traffic.generate(traffic.load_mix(path), 1, 5.0, 100, max_len=64,
                         max_new_cap=64)
    assert len(s.items) == 6
    assert [i.turn for i in s.items] == [0, 0, 1, 1, 2, 2]
    assert all(3 <= i.max_new <= 5 for i in s.items)
    path.write_text(json.dumps({**mix, "loop": "sideways"}))
    with pytest.raises(ValueError):
        traffic.load_mix(path)
