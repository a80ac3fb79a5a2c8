"""90th percentile, over the requests due in the window, of the time from
a request's due time to the end of the first engine step after which it is
no longer queued (host clock). The traced run stops its profiler only once
no such request is queued, so the stop delays none of them."""
import numpy as np


def read(rec):
    waits = rec["queue_wait_s"]
    if not waits:
        return None
    return float(np.percentile(np.asarray(waits, float), 90))
