"""Rank decision: share of the window's segment decisions in which the
Eq. 9 perturbation bound overrode the policy: 100 x the rise of the
engine counter ``rank_vetoes`` over the rise of ``decides``, in %.
Silent on a program without the counter and on a window with no
decision."""


def read(rec):
    c0, c1 = rec.counters0, rec.counters1
    if "rank_vetoes" not in c0 or "rank_vetoes" not in c1:
        return None
    n = c1["decides"] - c0["decides"]
    if n <= 0:
        return None
    return 100.0 * (c1["rank_vetoes"] - c0["rank_vetoes"]) / n
