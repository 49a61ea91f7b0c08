"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to the device's busy
time, its time per operation, and its idle gaps labelled by what the host
was doing.

- Device operations are the events of the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane.  Busy time is the union of their intervals
  inside the window, averaged over the chips; ``device_ops`` sums them by
  operation kind (``op_name``).
- The window is the host annotation ``bench.window`` (the measured
  stretch); without one, the whole trace.
- Host activity is every host-plane event whose name starts with one of
  ``LABELS``: the benchmark's own ``TraceAnnotation`` names and those it
  writes beside the program's ``obs`` spans and phase timers.
  ``idle_gaps`` gives, per label, the idle device time that fell inside
  that label's intervals, and ``(no host span)`` for the rest: a gap
  seen under two labels on two threads counts under each.  The trace
  aligns host and device clocks to about a millisecond (a recorded v5e
  trace shows device work starting up to 1.2 ms before its host
  dispatch), so gaps shorter than that are attributed loosely.
"""

from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

import numpy as np

_DEVICE = re.compile(r"^/device:TPU:\d+$")
_OP = re.compile(r"^%([A-Za-z_-]+)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
WINDOW = "bench.window"
TOP = 10
LABELS = ("bench.", "serve.batch", "scatter", "replica_read", "device_score",
          "merge", "bm25_topk.")


def union(iv: np.ndarray) -> np.ndarray:
    """Disjoint sorted union of [start, end) rows."""
    if not len(iv):
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.array(out, dtype=np.float64)


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def length(iv: np.ndarray) -> float:
    return float((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0.0


def overlap(a: np.ndarray, b: np.ndarray) -> float:
    """Total length of the intersection of two disjoint sorted unions."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            total += hi - lo
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return total


def complement(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    edges = [lo]
    for s, e in iv:
        edges += [s, e]
    edges.append(hi)
    gaps = np.array(edges, dtype=np.float64).reshape(-1, 2)
    return gaps[gaps[:, 1] > gaps[:, 0]]


def op_name(hlo: str) -> str:
    """A device event's HLO text cut to its operation kind (numbering and
    shapes dropped), with a custom call's target: ``fusion``,
    ``custom-call:TopK``."""
    m = _OP.match(hlo)
    name = m.group(1) if m else hlo[:40]
    t = _TARGET.search(hlo)
    return f"{name}:{t.group(1)}" if t else name


def _events(pd) -> Tuple[Dict[int, list], Dict[str, list]]:
    """(device ops per chip: [(name, start, end)], host label intervals)."""
    ops: Dict[int, list] = {}
    host: Dict[str, list] = defaultdict(list)
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            chip = int(plane.name.rsplit(":", 1)[1])
            lines = {line.name: line for line in plane.lines}
            line = lines.get("XLA Ops")
            ops[chip] = [] if line is None else [
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host[e.name].append((e.start_ns, e.start_ns
                                         + e.duration_ns))
    return ops, host


def reduce(path: str, labels: Sequence[str] = LABELS) -> dict:
    """``busy_s``, ``window_s``, ``device_ops`` and ``idle_gaps`` (each a
    list of at most 10 ``[name, seconds]``, largest first) of one trace.
    Raises when the trace holds no TPU plane."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    ops, host = _events(pd)
    if not ops:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    if host.get(WINDOW):
        lo, hi = max(host[WINDOW], key=lambda iv: iv[1] - iv[0])
    else:
        ends = [e for evs in ops.values() for _, _, e in evs]
        starts = [s for evs in ops.values() for _, s, _ in evs]
        lo, hi = (min(starts), max(ends)) if starts else (0.0, 0.0)
    window = hi - lo
    per_op: Dict[str, float] = defaultdict(float)
    gaps_by: Dict[str, float] = defaultdict(float)
    busy = []
    label_iv = {name: union(np.array(iv, dtype=np.float64))
                for name, iv in host.items()
                if name != WINDOW and name.startswith(tuple(labels))}
    for chip, evs in ops.items():
        iv = np.array([(s, e) for _, s, e in evs], dtype=np.float64)
        iv = union(clip(iv.reshape(-1, 2), lo, hi))
        busy.append(length(iv))
        for name, s, e in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                per_op[op_name(name)] += d / 1e9
        idle = complement(iv, lo, hi)
        covered = np.zeros((0, 2))
        for name, liv in label_iv.items():
            gaps_by[name] += overlap(idle, clip(liv, lo, hi)) / 1e9 / len(ops)
        if label_iv:
            covered = union(np.concatenate(
                [clip(v, lo, hi) for v in label_iv.values()]))
        free = length(idle) - overlap(idle, covered)
        gaps_by["(no host span)"] += free / 1e9 / len(ops)

    def top(d: Dict[str, float]) -> List[list]:
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                if v > 0][:TOP]

    return {"busy_s": float(np.mean(busy)) / 1e9, "window_s": window / 1e9,
            "device_ops": top(per_op), "idle_gaps": top(gaps_by)}


def find_xplane(directory: Path) -> Path:
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]
