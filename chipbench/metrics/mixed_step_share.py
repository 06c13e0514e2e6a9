"""Scheduler and chunked prefill: share of the window's fused steps that
carried a prefill chunk (engine counters ``mixed_steps`` / ``steps``), %."""


def read(rec):
    steps = rec.counters1["steps"] - rec.counters0["steps"]
    mixed = rec.counters1["mixed_steps"] - rec.counters0["mixed_steps"]
    return 100.0 * mixed / steps if steps > 0 else None
