"""Device: share of the traced stretch in which no operation ran on the
device, 1 - busy union / window, in % (``idle_share.<cell>``)."""


def read(rec):
    tr = rec.trace
    if tr is None or tr.window_s <= 0 or tr.n_devices == 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
