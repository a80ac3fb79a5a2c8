"""The chip's peaks and the roofline time of a piece of work. The operations
and bytes of the served work are counted by each configuration's block
(``blocks/<block>.py``: ``decode_flops``, ``prefill_flops``,
``decode_attn_work``, ``prefill_attn_work``), which the per-layer readers
find in the trace record (``rec["block"]``).
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device missing from ``peaks.json`` is
    an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "peaks.json")
    return table[device_kind]


def roofline_s(flops: float, byts: float, pk: dict, chips: int = 1
               ) -> float:
    """Least time the chips could take: the larger of operations over
    peak bf16 rate and bytes over peak HBM bandwidth."""
    return max(flops / (pk["bf16_flops"] * chips),
               byts / (pk["hbm_bytes_per_s"] * chips))
