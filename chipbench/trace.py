"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer readers
and the ``breakdown`` need, on the host's ``perf_counter`` clock.

- ``calls``: per jitted executable (by XLA module name), the device
  intervals of its runs, (start_s, dur_s).
- ``busy_s`` / ``window_s``: the union of the intervals in which an XLA
  operation ran on the device, averaged over the devices seen, within the
  traced window; and that window's length.
- ``top_ops``: device self time per operation (time in which no
  operation nested in it ran), most first, labelled
  ``<executable>/<op> <shape> <kind>``.
- ``idle_gaps``: the longest stretches with no operation on the device,
  each labelled with the host phase (``repro.obs`` step phases) that
  covers its middle, or ``host: outside a step phase``.

The host and device clocks of one trace share a time base; an annotation
the harness enters at a known ``perf_counter`` instant (``anchor``) maps
that base onto ``perf_counter``.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

import numpy as np

ANCHOR = "chipbench_anchor"
MODULE_LINES = ("XLA Modules",)
OP_LINES = ("XLA Ops",)


@dataclass
class Trace:
    calls: Dict[str, List[Tuple[float, float]]] = field(default_factory=dict)
    busy_s: float = 0.0
    window_s: float = 0.0
    window: Tuple[float, float] = (0.0, 0.0)
    top_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)
    n_devices: int = 0

    def calls_of(self, key: str) -> List[Tuple[float, float]]:
        """Runs of every executable whose module name contains ``key``."""
        out = []
        for name, runs in self.calls.items():
            if key in name:
                out.extend(runs)
        return sorted(out)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def union_length(iv: np.ndarray) -> Tuple[float, np.ndarray]:
    """Length of the union of intervals (n, 2) and the merged intervals."""
    if len(iv) == 0:
        return 0.0, np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0])]
    merged = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    m = np.asarray(merged)
    return float(np.sum(m[:, 1] - m[:, 0])), m


def module_name(name: str) -> str:
    """``jit__step_impl(1234)`` -> ``jit__step_impl``."""
    return re.sub(r"\(\d+\)$", "", name)


def op_label(name: str) -> str:
    """``%fusion.3 = f32[8,128]{1,0} fusion(...), kind=kLoop`` ->
    ``fusion.3 f32[8,128] fusion``."""
    lhs, _, rest = name.partition(" = ")
    lhs = lhs.lstrip("%")
    if not rest:
        return lhs[:80]
    if rest.startswith("("):
        depth, i = 0, 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        typ, tail = "(tuple)", rest[i + 1:].lstrip()
    else:
        typ, _, tail = rest.partition(" ")
        typ = re.sub(r"\{.*$", "", typ)
    kind = tail.split("(", 1)[0].strip()
    return f"{lhs} {typ[:60]} {kind}"


def self_times(events: List[Tuple[float, float, str]]
               ) -> Dict[str, float]:
    """Per name, the time its events ran with no nested event inside."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[List] = []          # [end, name, self_time]
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, n, t = stack.pop()
            out[n] += t
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    for end, n, t in stack:
        out[n] += t
    return out


def _is_device(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def label_gap(mid: float, phases: Sequence[Tuple[str, float, float]]) -> str:
    for name, s, e in phases:
        if s <= mid < e:
            return f"host: {name}"
    return "host: outside a step phase"


def reduce_trace(path: str, anchor_perf_s: float,
                 window: Tuple[float, float],
                 phases: Sequence[Tuple[str, float, float]] = (),
                 top: int = 10) -> Trace:
    """``window`` and ``phases`` (name, start, end) are perf_counter
    seconds; the result is too."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    anchor_ns = None
    for plane in pd.planes:
        if _is_device(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == ANCHOR:
                    anchor_ns = ev.start_ns
                    break
    if anchor_ns is None:
        raise ValueError("trace holds no anchor annotation")

    def to_s(ns: float) -> float:
        return (ns - anchor_ns) * 1e-9 + anchor_perf_s

    w0, w1 = window
    out = Trace(window=window, window_s=w1 - w0)
    calls: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    op_time: Dict[str, float] = defaultdict(float)
    busy, gaps = [], []
    for plane in pd.planes:
        if not _is_device(plane.name):
            continue
        ops, mods = [], []
        for line in plane.lines:
            if line.name in MODULE_LINES:
                for ev in line.events:
                    s = to_s(ev.start_ns)
                    if w0 <= s < w1:
                        mods.append((ev.name, s, ev.duration_ns * 1e-9))
            elif line.name in OP_LINES:
                for ev in line.events:
                    s = to_s(ev.start_ns)
                    d = ev.duration_ns * 1e-9
                    if s + d <= w0 or s >= w1:
                        continue
                    ops.append((max(s, w0), min(s + d, w1), ev.name))
        if not ops and not mods:
            continue
        out.n_devices += 1
        mods.sort(key=lambda m: m[1])
        starts = [m[1] for m in mods]
        for name, s, d in mods:
            calls[module_name(name)].append((s, d))
        labelled = []
        for s, e, name in ops:
            i = bisect.bisect_right(starts, s) - 1
            mod = module_name(mods[i][0]) if i >= 0 else "?"
            labelled.append((s, e, f"{mod}/{op_label(name)}"))
        for name, t in self_times(labelled).items():
            op_time[name] += t
        iv = np.asarray([(s, e) for s, e, _ in ops] if ops else
                        [(s, s + d) for _, s, d in mods], np.float64)
        b, merged = union_length(iv)
        busy.append(b)
        edges = np.concatenate([[w0], merged.ravel(), [w1]]).reshape(-1, 2)
        for s, e in edges:
            if e > s:
                gaps.append((e - s, (s + e) / 2))
    out.calls = dict(calls)
    out.busy_s = float(np.mean(busy)) if busy else 0.0
    n = max(out.n_devices, 1)
    out.top_ops = [(k, v / n) for k, v in
                   sorted(op_time.items(), key=lambda kv: -kv[1])[:top]]
    gaps.sort(reverse=True)
    out.idle_gaps = [(label_gap(mid, phases), g) for g, mid in gaps[:top]]
    return out
