"""Reduce a profiler trace to device busy and idle time, per-op and
per-program device time, and idle gaps attributed to host spans.

A trace is read into plain events ``(plane, line, name, start_ns, dur_ns)``:
the device planes' ``XLA Ops`` and ``XLA Modules`` lines, and the host
spans that the harness writes (names starting with ``bench:``). The
reduction works on that list alone, so it is checked on a small recorded
trace without a chip.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
from collections import defaultdict
from typing import Dict, List, Tuple

Event = Tuple[str, str, str, int, int]
OPS, MODULES = "XLA Ops", "XLA Modules"
# ops that contain other ops: counted in busy time, not in the per-op table
CONTAINERS = ("while", "conditional", "call")
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"


def load_xplane(log_dir: str) -> List[Event]:
    """Events of the newest ``*.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    out: List[Event] = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name in (OPS, MODULES):
                out.extend((plane.name, line.name, op_name(e.name),
                            int(e.start_ns), int(e.duration_ns))
                           for e in line.events)
            elif not device:
                out.extend((plane.name, line.name, e.name, int(e.start_ns),
                            int(e.duration_ns)) for e in line.events
                           if e.name.startswith(SPAN_PREFIX))
    return out


def op_name(text: str) -> str:
    """An HLO instruction's name (``%fusion.12 = bf16[...] ...`` ->
    ``fusion.12``); other names unchanged."""
    return text.split(" = ", 1)[0].lstrip("%")


def load_events(path: str) -> List[Event]:
    """Events saved as a gzipped JSON list (the recorded test trace)."""
    with gzip.open(path, "rt") as f:
        return [tuple(e) for e in json.load(f)]


def _union(intervals) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _clip(s: int, e: int, lo: int, hi: int):
    return max(s, lo), min(e, hi)


def window_of(events: List[Event]) -> Tuple[int, int]:
    """The traced window: the harness's ``bench:window`` span."""
    w = [(s, s + d) for _p, _l, n, s, d in events if n == WINDOW_SPAN]
    if not w:
        raise ValueError("trace holds no bench:window span")
    return w[0]


def reduce(events: List[Event], top: int = 10) -> Dict:
    """Device time inside the window, averaged over the device planes.

    Returns ``busy_s`` (union of op intervals), ``window_s``, ``ops``
    (name -> seconds, summed), ``op_events`` (name -> list of seconds),
    ``modules`` (name -> list of seconds), ``gaps`` (the ``top`` longest
    idle gaps as [host span, seconds]) and ``devices``.
    """
    lo, hi = window_of(events)
    by_plane: Dict[str, List[Tuple[int, int]]] = defaultdict(list)
    ops: Dict[str, float] = defaultdict(float)
    op_events: Dict[str, List[float]] = defaultdict(list)
    modules: Dict[str, List[float]] = defaultdict(list)
    spans = []
    for plane, line, name, s, d in events:
        if name.startswith(SPAN_PREFIX):
            if name != WINDOW_SPAN:
                spans.append((s, s + d, name))
            continue
        a, b = _clip(s, s + d, lo, hi)
        if b <= a:
            continue
        if line == OPS:
            by_plane[plane].append((a, b))
            if not name.startswith(CONTAINERS):
                ops[name] += (b - a) * 1e-9
                op_events[name].append((b - a) * 1e-9)
        elif line == MODULES:
            modules[name].append((b - a) * 1e-9)
    n_dev = max(len(by_plane), 1)
    busy = 0
    gaps = []
    for plane, iv in by_plane.items():
        merged = _union(iv)
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [x for s, e in merged for x in (s, e)] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                gaps.append((g1 - g0, g0, g1))
    gaps.sort(reverse=True)
    named = [[host_span(spans, (g0 + g1) // 2), dur * 1e-9]
             for dur, g0, g1 in gaps[:top]]
    return {
        "busy_s": busy * 1e-9 / n_dev,
        "window_s": (hi - lo) * 1e-9,
        "ops": {k: v / n_dev for k, v in ops.items()},
        "op_events": dict(op_events),
        "modules": dict(modules),
        "gaps": named,
        "devices": len(by_plane),
    }


def host_span(spans, t: int) -> str:
    """Name of the innermost (shortest) host span covering ``t``."""
    best = None
    for s, e, name in spans:
        if s <= t < e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1][len(SPAN_PREFIX):] if best else "outside any span"


def top_ops(red: Dict, top: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(red["ops"].items(),
                                      key=lambda kv: -kv[1])[:top]]


def select(table: Dict[str, list], pattern: str) -> list:
    """Every value of keys containing ``pattern``, concatenated."""
    return [x for k, v in table.items() if pattern in k for x in v]
