"""Model FLOPs of the traced prefills (the prompt tokens computed, not the
padding) over their device time, as a share of the chips' bf16 peak."""
import trace_reduce as T


def read(rec):
    if rec["trace"] is None or not rec["prefill_calls"]:
        return None
    secs, n = T.program_time(rec["trace"], "prefill")
    calls = rec["prefill_calls"][:n]
    if secs <= 0 or not calls:
        return None
    work = sum(rec["block"].prefill_flops(rec["model"], c) for c in calls)
    return 100.0 * work / (secs * rec["chips"]
                           * rec["peaks"]["bf16_flops"]) * n / len(calls)
