"""Roofline time of the decode attention the live tokens need (their K/V
read once at the cell's KV dtype, plus q and out) over the device time of
the paged-attention kernel in the traced decode steps."""
import flops as F
import trace_reduce as T


def read(rec):
    if rec["trace"] is None or not rec["decode_calls"]:
        return None
    _, n = T.program_time(rec["trace"], "decode")
    calls = rec["decode_calls"][:n]
    secs = T.op_time(rec["trace"], "decode", T.DECODE_KERNELS)
    if secs <= 0 or not calls:
        return None
    fl = by = 0.0
    for c in calls:
        f, b = rec["block"].decode_attn_work(rec["model"], c,
                                             rec["kv_bytes"])
        fl, by = fl + f, by + b
    best = F.roofline_s(fl, by, rec["peaks"], rec["chips"])
    return 100.0 * best / secs
