"""Host loop: host time per fused step outside the token fetch, in ms,
from the program's always-on step-phase counters (``serve.phase.<p>_s``,
in the engine's stats as ``phase.<p>_s``) at the window's open and close:
the seconds of every phase but ``fetch``, over the steps. Silent on a
program without the counters."""

KEYS = tuple(f"phase.{p}_s" for p in
             ("schedule", "admit", "decide", "dispatch", "deliver"))


def read(rec):
    c0, c1 = rec.counters0, rec.counters1
    if not all(k in c0 and k in c1 for k in KEYS):
        return None
    steps = c1["steps"] - c0["steps"]
    if steps <= 0:
        return None
    return 1e3 * sum(c1[k] - c0[k] for k in KEYS) / steps
