"""Preemptions in the window (the engine's counter) per 100 requests
admitted in it."""


def read(rec):
    if not rec["admitted"]:
        return None
    return 100.0 * rec["preempted"] / rec["admitted"]
