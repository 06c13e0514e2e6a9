"""Where a fused step's device time goes, by the program's named scopes,
and the program's host step phases read from the profiler's own trace.

The program wraps the parts of its fused steps in ``jax.named_scope``
blocks (``repro.models.transformer.decode_step_paged``: ``embed``,
``layers`` and in it ``qkv``, ``kv_write``, ``kv_read``, ``rank_proj``,
``attend``, ``mass``, ``out_proj``, ``ffn``, then ``head``; the engine's
``sample``; ``decide``'s ``gather_k``, ``gram``, ``eigh``, ``reproject``)
and runs each host step phase inside a profiler annotation
``serve.<phase>``. This module

- maps each instruction of an executable's optimized HLO text
  (``Engine.compiled_text()``) to the innermost scope in its ``op_name``
  metadata (``scope_map``). The layer scan's own
  ``while/body/dynamic_slice`` and ``dynamic_update_slice`` (each layer's
  slice of the stacked pools and weights in, its pools out) are the scope
  ``pool_io``. An instruction with no scope finer than ``layers`` takes
  the scope of its one operand, or else of its one user, one hop each way:
  that covers the copies and bitcasts layout assignment inserts, and a
  copy next to ``pool_io`` is ``pool_io``;
- reduces a trace to device self time per scope per executable, over the
  runs of each executable that start in the traced window
  (``scope_times``). The self time is ``exclusive_times``: like
  ``chipbench.trace.self_times`` for nested ops, but an op that only
  partly overlaps the one before it, as a TPU's ops do, is not counted
  twice, so a step's scopes sum to the union of its ops. ``group_ms``
  sums a step's scopes into ``GROUPS``;
- reads the ``serve.<phase>`` annotations on the ``perf_counter`` clock
  (``phase_spans``), for ``chipbench.trace.reduce_trace`` to label idle
  gaps from the trace alone.

    python3 -m chipbench.scopes --workload <cell> --seeds <n>[,<n>...] \\
        [--seconds <s>] [--trace 0|1] [--obs-trace 0|1] [--keep <prefix>] \\
        [--root <dir>]

runs the cell as ``chipbench.run`` does, one run per seed in one process,
and prints each run's result line with a ``scoped`` object added: the
groups of ``_step_mixed_impl`` per run, each executable's time per scope,
the ten longest unscoped ops, the idle gaps labelled from the annotations
and from the ``repro.obs`` spans, and host time per step from the phase
counters and from the spans. ``--obs-trace`` sets ``repro.obs`` span
tracing apart from the profiler (default: on with ``--trace``).
``--keep <prefix>`` writes ``<prefix>.xplane.pb.gz``, ``<prefix>.meta.json``
(anchor, window, phases from the spans) and ``<prefix>.hlo.json.gz`` (the
HLO texts), as the test fixture under ``tests/chipbench/fixtures/`` was
recorded.
"""
from __future__ import annotations

import argparse
import bisect
import gzip
import heapq
import json
import re
import shutil
import sys
import weakref
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench.trace import (ANCHOR, MODULE_LINES, OP_LINES, _is_device,
                             find_xplane, module_name, op_label, reduce_trace)

SCOPES = ("embed", "layers", "qkv", "kv_write", "kv_read", "rank_proj",
          "attend", "mass", "out_proj", "ffn", "head", "sample",
          "gather_k", "gram", "eigh", "reproject")
POOL_IO = "pool_io"
# the fused step's scopes, summed into the groups its metrics name; time
# in no group is "unscoped"
GROUPS = {
    "pool_io": (POOL_IO,),
    "kv": ("kv_write", "kv_read"),
    "attn": ("rank_proj", "attend", "mass"),
    "dense": ("embed", "qkv", "out_proj", "ffn", "head", "sample"),
}
MIXED = "jit__step_mixed_impl"
PHASE_PREFIX = "serve."

# never an op of its own, or the first half of one: a hop looks through
_THROUGH = {"get-tuple-element", "tuple", "bitcast", "copy-start"}
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)$")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_of(op_name: str) -> Optional[str]:
    """The innermost program scope in an ``op_name`` path; ``pool_io`` for
    the layer scan's own slices in and updates out."""
    parts = op_name.split("/")
    for i in range(len(parts) - 1, -1, -1):
        if parts[i] in SCOPES:
            if parts[i + 1:] in (["while", "body", "dynamic_slice"],
                                 ["while", "body", "dynamic_update_slice"]):
                return POOL_IO
            return parts[i]
    return None


def _close(s: str, i: int) -> int:
    """Index of the parenthesis closing the one at ``s[i]``."""
    depth = 0
    for j in range(i, len(s)):
        depth += s[j] == "("
        depth -= s[j] == ")"
        if depth == 0:
            return j
    return len(s)


def instructions(text: str):
    """(name, opcode, operands, op_name or None) of every instruction in
    an HLO module's text, fused computations included."""
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        name, rest = m.groups()
        if rest.startswith("("):                 # a tuple type
            rest = rest[_close(rest, 0) + 1:].lstrip()
        else:
            rest = rest.partition(" ")[2]
        i = rest.find("(")
        args = rest[i:_close(rest, i)] if i >= 0 else ""
        meta = _OP_NAME.search(line)
        yield (name, rest[:i] if i >= 0 else rest, _OPERAND.findall(args),
               meta.group(1) if meta else None)


def scope_map(text: str) -> Dict[str, Optional[str]]:
    """Instruction name -> scope, with the one-hop rule for instructions
    with no scope finer than ``layers`` (module docstring). A hop looks
    through instructions that never run as an op of their own
    (``get-tuple-element``, ``tuple``, ``bitcast``) and through the
    ``copy-start`` half of an async copy; reaching the layer scan's
    ``while`` itself reads as ``pool_io``, as what is copied in and out
    of it is the loop-carried stacked pools."""
    ins = {n: (op, ops, scope_of(o) if o else None)
           for n, op, ops, o in instructions(text)}
    users: Dict[str, set] = defaultdict(set)
    for n, (_, ops, _) in ins.items():
        for o in ops:
            users[o].add(n)

    def seen(n: str, up: bool) -> Optional[str]:
        for _ in range(len(ins)):
            if n not in ins:
                return None
            op, ops, sc = ins[n]
            if op == "while" and sc == "layers":
                return POOL_IO
            if op not in _THROUGH:
                return sc
            nxt = set(ops) if up else users[n]
            if len(nxt) != 1:
                return None
            n = next(iter(nxt))
        return None

    out = {}
    for n, (op, ops, sc) in ins.items():
        if sc in (None, "layers") and op != "while":
            near = []
            if len(set(ops)) == 1:
                near.append(seen(ops[0], True))
            if len(users[n]) == 1:
                near.append(seen(next(iter(users[n])), False))
            near = [x for x in near if x not in (None, "layers")]
            if near:
                sc = POOL_IO if POOL_IO in near else near[0]
        out[n] = sc
    return out


def exclusive_times(events: Sequence[Tuple[float, float, object]]
                    ) -> Dict[object, float]:
    """Per name, the time in which its (start, end, name) event is the
    latest-started one still running: nested events count as in
    ``chipbench.trace.self_times``, and events that only partly overlap,
    as consecutive ops on a TPU's op line do, are counted once, so the
    times sum to the union of the events."""
    evs = sorted(events, key=lambda x: (x[0], -x[1]))
    points = sorted({t for s, e, _ in evs for t in (s, e)})
    out: Dict[object, float] = defaultdict(float)
    live: List = []                          # (-start order, end, name)
    i = 0
    for a, b in zip(points, points[1:]):
        while i < len(evs) and evs[i][0] <= a:
            heapq.heappush(live, (-i, evs[i][1], evs[i][2]))
            i += 1
        while live and live[0][1] <= a:
            heapq.heappop(live)
        if live:
            out[live[0][2]] += b - a
    return out


def _clock(pd, anchor_perf_s: float):
    """Profiler nanoseconds -> perf_counter seconds, through the harness's
    anchor annotation."""
    for plane in pd.planes:
        if _is_device(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == ANCHOR:
                    a = ev.start_ns
                    return lambda ns: (ns - a) * 1e-9 + anchor_perf_s
    raise ValueError("trace holds no anchor annotation")


def phase_spans(path: str, anchor_perf_s: float
                ) -> List[Tuple[str, float, float]]:
    """The program's ``serve.<phase>`` annotations in a trace as (phase,
    start, end), perf_counter seconds, in start order."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    to_s = _clock(pd, anchor_perf_s)
    out = []
    for plane in pd.planes:
        if _is_device(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PHASE_PREFIX):
                    s = to_s(ev.start_ns)
                    out.append((ev.name[len(PHASE_PREFIX):], s,
                                s + ev.duration_ns * 1e-9))
    return sorted(out, key=lambda p: p[1])


def device_events(path: str, anchor_perf_s: float) -> List[Tuple]:
    """Per device plane of a trace: (module runs as (start_s, dur_s,
    module name), ops as (start_s, dur_s, XLA Ops event name)),
    perf_counter seconds."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    to_s = _clock(pd, anchor_perf_s)
    out = []
    for plane in pd.planes:
        if not _is_device(plane.name):
            continue
        mods, ops = [], []
        for line in plane.lines:
            if line.name in MODULE_LINES:
                mods += [(to_s(ev.start_ns), ev.duration_ns * 1e-9,
                          module_name(ev.name)) for ev in line.events]
            elif line.name in OP_LINES:
                ops += [(to_s(ev.start_ns), ev.duration_ns * 1e-9, ev.name)
                        for ev in line.events]
        if mods:
            out.append((mods, ops))
    return out


def scope_times(devices: Sequence[Tuple], maps: Dict[str, Dict],
                window: Tuple[float, float]) -> Dict[str, Dict]:
    """Per executable (module name): ``runs`` that start in ``window``,
    and the device self time in seconds of the ops of those runs per
    scope (``scopes``; None for none) and per op (``ops``: name ->
    (scope, seconds)), averaged over ``devices`` (``device_events``).
    ``maps``: module name -> ``scope_map`` of its HLO text."""
    w0, w1 = window
    op_s: Dict[Tuple[str, str], float] = defaultdict(float)
    runs: Dict[str, int] = defaultdict(int)
    for mods, ops in devices:
        mods = sorted(mods)
        starts = [m[0] for m in mods]
        for s, _, mod in mods:
            runs[mod] += w0 <= s < w1
        labelled = []
        for s, d, name in ops:
            i = bisect.bisect_right(starts, s) - 1
            if i < 0:
                continue
            ms, md, mod = mods[i]
            if w0 <= ms < w1 and s < ms + md:
                labelled.append((s, s + d, (mod, op_label(name).split()[0])))
        for key, t in exclusive_times(labelled).items():
            op_s[key] += t
    n = max(len(devices), 1)
    out: Dict[str, Dict] = {}
    for (mod, op), t in op_s.items():
        e = out.setdefault(mod, {"runs": runs[mod] / n,
                                 "scopes": defaultdict(float), "ops": {}})
        sc = maps.get(mod, {}).get(op)
        e["scopes"][sc] += t / n
        e["ops"][op] = (sc, t / n)
    return out


def group_ms(entry: Dict) -> Dict[str, float]:
    """One executable's ``scope_times`` entry as ms per run in each of
    ``GROUPS``, the step's self time per run (``step_ms``) and the share
    of it in no group (``unscoped_share``, %)."""
    n = entry["runs"]
    if not n:
        return {}
    total = sum(entry["scopes"].values())
    out = {g: 1e3 * sum(entry["scopes"].get(s, 0.0) for s in members) / n
           for g, members in GROUPS.items()}
    grouped = sum(out.values()) * n / 1e3
    out["step_ms"] = 1e3 * total / n
    out["unscoped_share"] = 100.0 * (total - grouped) / total if total else 0.0
    return out


def unscoped_ops(entry: Dict, top: int = 10) -> List[Tuple[str, float]]:
    """The ops in no group, most self time first, ms per run."""
    member = {s for ms in GROUPS.values() for s in ms}
    n = max(entry["runs"], 1)
    rest = [(op, 1e3 * t / n) for op, (sc, t) in entry["ops"].items()
            if sc not in member]
    return sorted(rest, key=lambda x: -x[1])[:top]


def gap_labels(path: str, anchor_perf_s: float, window: Tuple[float, float],
               phases: Sequence[Tuple[str, float, float]]) -> Dict:
    """The ten longest idle gaps labelled from the trace's own
    ``serve.<phase>`` annotations and from ``phases`` (the ``repro.obs``
    spans), and on how many of them the two labels agree."""
    from_ann = reduce_trace(path, anchor_perf_s, window,
                            phase_spans(path, anchor_perf_s)).idle_gaps
    from_obs = reduce_trace(path, anchor_perf_s, window, phases).idle_gaps
    return {"annotations": from_ann, "spans": from_obs,
            "agree": sum(a[0] == b[0] for a, b in zip(from_ann, from_obs)),
            "of": len(from_ann)}


def host_step_from_spans(spans: List[Dict], t0: float, t1: float):
    """Host seconds per step outside ``fetch`` from the ``repro.obs``
    phase spans that start in [t0, t1), ms (None without spans)."""
    tot, steps = 0.0, set()
    for e in spans:
        if e.get("cat") == "phase" and t0 <= e["abs_s"] < t1:
            steps.add(e["args"]["step"])
            if e["name"] != "fetch":
                tot += e["dur"] * 1e-3
    return tot / len(steps) if steps else None


def _keep(prefix: Path, path: str, texts: Dict[str, str], anchor: float,
          window: Tuple[float, float], phases: List[Tuple]) -> None:
    prefix.parent.mkdir(parents=True, exist_ok=True)
    with open(path, "rb") as src, \
            gzip.open(f"{prefix}.xplane.pb.gz", "wb") as dst:
        shutil.copyfileobj(src, dst)
    with gzip.open(f"{prefix}.hlo.json.gz", "wt") as f:
        json.dump(texts, f)
    Path(f"{prefix}.meta.json").write_text(json.dumps(
        {"anchor": anchor, "window": list(window), "phases": phases}))


def run_scoped(workload: str, seed: int, seconds: float, trace: bool,
               obs_trace: bool, *, keep: Optional[Path] = None,
               **kw) -> Dict:
    """One run of a cell through ``chipbench.run.run_cell``, with the
    ``scoped`` readings added (module docstring)."""
    from chipbench import run
    from chipbench.metrics import host_step_ms
    got: Dict = {}

    def hook(eng):
        got["eng"] = weakref.ref(eng)
        if obs_trace != trace:
            from repro.obs import SpanTracer
            eng.obs.tracer = (SpanTracer(pid=eng.obs.engine_id)
                              if obs_trace else None)

    class Rec(run.Record):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            got["rec"] = self
            # an untraced run keeps no spans; the engine still holds them
            got["spans"] = self.spans or run._spans(got["eng"]())

    record, reduce_profile = run.Record, run.reduce_profile

    def reduce_keeping(prof, spans):
        if "raw" in prof:
            log_dir, anchor, window = prof["raw"]
            path = find_xplane(log_dir)
            texts = got["eng"]().compiled_text()
            times = scope_times(device_events(path, anchor),
                                {m: scope_map(t) for m, t in texts.items()},
                                window)
            phases = [(e["name"], e["abs_s"], e["abs_s"] + e["dur"] * 1e-6)
                      for e in spans if e.get("cat") == "phase"]
            got["scoped"] = {
                "groups": group_ms(times.get(MIXED, {"runs": 0})),
                "unscoped_ops": (unscoped_ops(times[MIXED])
                                 if MIXED in times else []),
                "scope_ms": {m: {str(s): 1e3 * t / max(e["runs"], 1)
                                 for s, t in e["scopes"].items()}
                             for m, e in times.items()},
                "runs": {m: e["runs"] for m, e in times.items()},
                "gaps": gap_labels(path, anchor, window, phases)}
            if keep is not None:
                _keep(keep, path, texts, anchor, window, phases)
        return reduce_profile(prof, spans)

    run.Record, run.reduce_profile = Rec, reduce_keeping
    try:
        res = run.run_cell(workload, seed, seconds, trace, fault=hook, **kw)
    finally:
        run.Record, run.reduce_profile = record, reduce_profile
    rec = got["rec"]
    scoped = got.get("scoped", {})
    scoped["host_step_ms"] = {
        "counters": host_step_ms.read(rec),
        "spans": host_step_from_spans(got["spans"], rec.t0, rec.t1)}
    res["scoped"] = scoped
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--obs-trace", type=int, choices=(0, 1), default=None)
    ap.add_argument("--keep", type=Path, default=None)
    ap.add_argument("--root", type=Path, default=None,
                    help="directory of the BENCHMARK.json to read "
                         "(default: this checkout's)")
    args = ap.parse_args(argv)
    from chipbench.run import Refused
    obs = bool(args.trace if args.obs_trace is None else args.obs_trace)
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            res = run_scoped(args.workload, seed, args.seconds,
                             bool(args.trace), obs, keep=args.keep,
                             **({"root": args.root} if args.root else {}))
        except Refused as e:
            print(f"chipbench: {e}", file=sys.stderr)
            return e.code
        print(json.dumps({"seed": seed, "obs_trace": obs, **res}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
