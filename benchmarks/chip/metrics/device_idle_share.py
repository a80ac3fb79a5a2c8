"""Share of the traced window in which no operation ran on the device
(averaged over the cell's chips): 100 * (1 - busy / window)."""
import trace_reduce as T


def read(rec):
    if rec["trace"] is None:
        return None
    busy, window = T.busy_share(rec["trace"])
    if window <= 0 or busy <= 0:
        return None
    return 100.0 * (1.0 - busy / window)
