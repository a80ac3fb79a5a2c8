"""95th percentile of every gap between consecutive output tokens whose
later token fell in the window (host clock): the stall a stream sees when
a prefill runs between its decode steps. The same number as the untraced
run's p95, read here per layer because it follows the order in which the
seed sends the requests more than the code."""


def read(rec):
    if not rec["itl_gaps"]:
        return None
    return rec["itl_p95_ms"]
