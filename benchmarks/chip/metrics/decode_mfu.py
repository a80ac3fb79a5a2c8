"""Model FLOPs of the traced decode steps over their device time, as a
share of the chips' bf16 peak: the whole decode step's utilization."""
import trace_reduce as T


def read(rec):
    if rec["trace"] is None or not rec["decode_calls"]:
        return None
    secs, n = T.program_time(rec["trace"], "decode")
    calls = rec["decode_calls"][:n]
    if secs <= 0 or not calls:
        return None
    work = sum(rec["block"].decode_flops(rec["model"], c) for c in calls)
    return 100.0 * work / (secs * rec["chips"]
                           * rec["peaks"]["bf16_flops"]) * n / len(calls)
