"""Rank decision: share of the factor width the policy asks for, over the
window's segment decisions: 100 x the rise of the engine counter
``rank_chosen_sum`` (the rank each decision chose, summed) over the rise
of ``decides`` times ``r_keep``, in %. Silent on a program without the
counter and on a window with no decision."""


def read(rec):
    c0, c1 = rec.counters0, rec.counters1
    if "rank_chosen_sum" not in c0 or "rank_chosen_sum" not in c1:
        return None
    n = c1["decides"] - c0["decides"]
    if n <= 0:
        return None
    chosen = c1["rank_chosen_sum"] - c0["rank_chosen_sum"]
    return 100.0 * chosen / (n * rec.spec.r_keep)
