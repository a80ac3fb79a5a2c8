"""Roofline time of causal attention over the prompt tokens computed (not
the padding) over the device time of the flash-prefill kernel in the
traced prefills."""
import flops as F
import trace_reduce as T


def read(rec):
    if rec["trace"] is None or not rec["prefill_calls"]:
        return None
    _, n = T.program_time(rec["trace"], "prefill")
    calls = rec["prefill_calls"][:n]
    secs = T.op_time(rec["trace"], "prefill", T.PREFILL_KERNELS)
    if secs <= 0 or not calls:
        return None
    fl = by = 0.0
    for c in calls:
        f, b = rec["block"].prefill_attn_work(rec["model"], c,
                                              rec["kv_bytes"])
        fl, by = fl + f, by + b
    best = F.roofline_s(fl, by, rec["peaks"], rec["chips"])
    return 100.0 * best / secs
