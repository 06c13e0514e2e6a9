"""Front end and scheduler: 95th percentile, over the requests due in the
window, of admission (the ``repro.obs`` ``request`` span's begin) minus
the time the request was due, in ms."""
import numpy as np


def read(rec):
    admit = {}
    for e in rec.spans:
        if e.get("name") == "request" and e.get("ph") == "b":
            admit.setdefault(int(e["id"]), e["abs_s"])
    wait = [(admit[r.rid] - r.due) * 1e3 for r in rec.requests
            if r.in_window and r.rid in admit]
    return float(np.percentile(wait, 95)) if wait else None
