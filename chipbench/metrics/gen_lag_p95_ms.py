"""Load generator: 95th percentile, over the requests due in the window,
of how late the generator submitted them (submit minus due), in ms."""
import numpy as np


def read(rec):
    lag = [(r.submit - r.due) * 1e3 for r in rec.requests
           if r.in_window and r.submit is not None]
    return float(np.percentile(lag, 95)) if lag else None
