"""Fused pure-decode step: mean device time per run of the ``_step_impl``
executable in the traced stretch, in ms."""
import numpy as np


def read(rec):
    runs = rec.trace.calls_of("_step_impl") if rec.trace else []
    return float(np.mean([d for _, d in runs]) * 1e3) if runs else None
