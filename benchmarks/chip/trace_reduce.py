"""Reduction of a profiler trace to what the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
three things, as plain lists that ``save``/``read`` move through JSON (the
tests run on a small recorded one):

    devices  per TPU: its program executions (line "XLA Modules") and its
             operations (line "XLA Ops"), each ``[name, start_ns, end_ns]``;
             an operation's name is its HLO instruction name without the
             ``%`` and the ``.N`` suffix (``paged_decode_attention``,
             ``copy``, ``fusion``), so a kernel is found by the name of the
             function that launched it
    spans    the harness's host spans (``bench.*``), ``[name, start, end]``
    window   ``[start_ns, end_ns]`` of the span ``bench.traced``

Programs are told apart by what runs inside them: an execution that holds a
paged-attention kernel is a decode step, one that holds a flash-prefill
kernel is a prefill.
"""
from __future__ import annotations

import gzip
import json
import re
from typing import Dict, List, Optional, Sequence, Tuple

DECODE_KERNELS = ("paged_decode_attention", "paged_qdecode_attention",
                  "paged_q4decode_attention")
PREFILL_KERNELS = ("flash_prefill_attention", "flash_qprefill_attention",
                   "flash_q4prefill_attention")
#: operations that only contain others (their time is their children's)
CONTAINERS = ("while", "conditional", "call")
#: host spans, innermost first: the label an idle gap gets
SPAN_LABELS = (("bench.prefill", "admit/prefill"),
               ("bench.admit", "admit/prefill"),
               ("bench.decode", "decode call"),
               ("bench.step", "harvest"),
               ("bench.submit", "submit"),
               ("bench.wait", "waiting for arrivals"))

_OP = re.compile(r"^%?([A-Za-z_][\w\-]*?)(\.\d+)?(\s*=.*)?$", re.S)
#: a cross-chip collective's operation in an instruction's text
_COLLECTIVE = re.compile(r"\s((?:all-reduce|all-gather|reduce-scatter|"
                         r"collective-permute|all-to-all)(?:-start|-done)?)"
                         r"\(")


def op_name(hlo: str) -> str:
    """``%paged_decode_attention.9 = f32[...] custom-call(...)`` ->
    ``paged_decode_attention``; a collective by its operation, whatever
    JAX named it (``%psum.36 = bf16[...] all-reduce(...)`` ->
    ``all-reduce``)."""
    head, _, rest = hlo.partition(" = ")
    c = _COLLECTIVE.search(" " + rest)
    if c:
        return c.group(1)
    m = _OP.match(head.strip())
    return m.group(1) if m else head.strip()


def load(path: str) -> dict:
    """Summary of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"name": plane.name, "modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key is None:
                    continue
                for e in line.events:
                    name = e.name if key == "modules" else op_name(e.name)
                    dev[key].append([name, int(e.start_ns), int(e.end_ns)])
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append([e.name, int(e.start_ns),
                                      int(e.end_ns)])
    devices.sort(key=lambda d: int(d["name"].rsplit(":", 1)[1]))
    spans.sort(key=lambda s: (s[1], s[2]))
    traced = [s for s in spans if s[0] == "bench.traced"]
    if traced:
        window = [traced[0][1], traced[-1][2]]
    else:
        ev = [e for d in devices for e in d["ops"]]
        window = ([min(e[1] for e in ev), max(e[2] for e in ev)]
                  if ev else [0, 0])
    return {"devices": devices, "spans": spans, "window": window}


def save(summary: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(summary, f)


def read(path: str) -> dict:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


# ------------------------------------------------------------------ #
def union(intervals: Sequence[Sequence[int]],
          lo: Optional[int] = None, hi: Optional[int] = None
          ) -> List[Tuple[int, int]]:
    """Merged intervals, clipped to [lo, hi] where given."""
    out: List[Tuple[int, int]] = []
    for a, b in sorted((x[-2], x[-1]) for x in intervals):
        if lo is not None:
            a = max(a, lo)
        if hi is not None:
            b = min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_ns(dev: dict, window: Sequence[int]) -> int:
    """Nanoseconds of ``window`` in which an operation ran on ``dev``."""
    return sum(b - a for a, b in union(dev["ops"], *window))


def busy_share(summary: dict) -> Tuple[float, float]:
    """(busy seconds averaged over the devices, window seconds)."""
    lo, hi = summary["window"]
    devs = summary["devices"]
    if not devs or hi <= lo:
        return 0.0, 0.0
    busy = sum(busy_ns(d, (lo, hi)) for d in devs) / len(devs)
    return busy / 1e9, (hi - lo) / 1e9


def programs(dev: dict) -> List[dict]:
    """Each program execution on ``dev`` with its kind (decode, prefill or
    other) and the operations that ran inside it."""
    ops = sorted(dev["ops"], key=lambda e: e[1])
    out, j = [], 0
    for name, a, b in sorted(dev["modules"], key=lambda e: e[1]):
        while j < len(ops) and ops[j][1] < a:
            j += 1
        k, inside = j, []
        while k < len(ops) and ops[k][1] < b:
            inside.append(ops[k])
            k += 1
        names = {o[0] for o in inside}
        kind = ("decode" if names.intersection(DECODE_KERNELS)
                else "prefill" if names.intersection(PREFILL_KERNELS)
                else "other")
        out.append({"name": name, "start": a, "end": b, "kind": kind,
                    "ops": inside})
    return out


def _in_window(p: dict, window: Sequence[int]) -> bool:
    return p["start"] >= window[0] and p["end"] <= window[1]


def program_time(summary: dict, kind: str) -> Tuple[float, int]:
    """(device seconds of ``kind`` programs averaged over the devices,
    executions per device) inside the traced window."""
    devs = summary["devices"]
    if not devs:
        return 0.0, 0
    secs, counts = 0.0, []
    for d in devs:
        ps = [p for p in programs(d) if p["kind"] == kind
              and _in_window(p, summary["window"])]
        secs += sum(p["end"] - p["start"] for p in ps) / 1e9
        counts.append(len(ps))
    return secs / len(devs), min(counts)


def op_time(summary: dict, kind: str, names: Sequence[str]) -> float:
    """Device seconds of the operations called ``names`` (prefixes) inside
    ``kind`` programs, averaged over the devices."""
    devs = summary["devices"]
    if not devs:
        return 0.0
    total = 0
    for d in devs:
        for p in programs(d):
            if p["kind"] != kind or not _in_window(p, summary["window"]):
                continue
            total += sum(b - a for n, a, b in p["ops"]
                         if n.startswith(tuple(names)))
    return total / len(devs) / 1e9


def top_ops(summary: dict, n: int = 10) -> List[list]:
    """The ``n`` operation names that took most device time (containers
    left out), in seconds averaged over the devices."""
    lo, hi = summary["window"]
    tot: Dict[str, float] = {}
    devs = summary["devices"]
    for d in devs:
        for name, a, b in d["ops"]:
            if name.startswith(CONTAINERS) or a < lo or b > hi:
                continue
            tot[name] = tot.get(name, 0.0) + (b - a) / 1e9 / len(devs)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def label_at(spans: Sequence[Sequence], t: int) -> str:
    """What the host was doing at ``t``: the innermost harness span."""
    covering = {s[0] for s in spans if s[1] <= t < s[2]}
    for name, label in SPAN_LABELS:
        if name in covering:
            return label
    return "other"


def idle_gaps(summary: dict, n: int = 10) -> List[list]:
    """The ``n`` longest gaps in which no operation ran on the first
    device, each ``[label, seconds]`` by what the host was doing at its
    middle."""
    devs = summary["devices"]
    if not devs:
        return []
    lo, hi = summary["window"]
    busy = union(devs[0]["ops"], lo, hi)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    spans = summary["spans"]
    gaps.sort(key=lambda g: -(g[1] - g[0]))
    return [[label_at(spans, (a + b) // 2), (b - a) / 1e9]
            for a, b in gaps[:n]]


def idle_by_label(summary: dict) -> Dict[str, float]:
    """Idle seconds of the first device, summed by host label."""
    devs = summary["devices"]
    if not devs:
        return {}
    lo, hi = summary["window"]
    out: Dict[str, float] = {}
    t = lo
    spans = summary["spans"]
    for a, b in union(devs[0]["ops"], lo, hi) + [(hi, hi)]:
        if a > t:
            lab = label_at(spans, (t + a) // 2)
            out[lab] = out.get(lab, 0.0) + (a - t) / 1e9
        t = max(t, b)
    return out
