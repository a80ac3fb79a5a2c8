"""Device time of the collective operations (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all, their ``-start``/``-done``
halves included) inside the traced decode steps, averaged over the chips,
per decode step: the cross-chip combine of a tensor-parallel step.
``trace_reduce.op_name`` names a collective by its operation."""
import trace_reduce as T

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def read(rec):
    if rec["trace"] is None:
        return None
    _, n = T.program_time(rec["trace"], "decode")
    secs = T.op_time(rec["trace"], "decode", COLLECTIVES)
    if n == 0 or secs <= 0:
        return None
    return 1e3 * secs / n
