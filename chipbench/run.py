"""Run one cell of ``BENCHMARK.json`` once and print its result line.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Set-up: the model's weights from the seed (one jitted call on the chip,
in the served dtype), a random DR-RL agent, one ``repro.serve.api.Engine``
behind a ``repro.serve.frontend.FrontEnd`` (which compiles the fused
steps and ``decide``), the eager shapes the cell's traffic uses, then the
traffic's own set-up: an open loop's ramp of arrivals, a closed loop's
long contexts. The window then runs for ``--seconds``; every request goes
in through ``FrontEnd.submit`` and its tokens come back through the
handle's ``on_token`` callback, stamped on the host clock.

After the window: the device's peak memory is read, the engine is shut
down and freed, and a sample of the served requests (drawn from the
seed, the one with most served tokens always in it) is compared with the
plain reference in ``chipbench/model.py``. ``--trace 1`` also profiles a
few seconds in the middle of the window and reports the cell's per-layer
metrics instead of its end-to-end ones.

The last line of standard output is the result; the numbers compared,
each with its limit, are the last lines of standard error and the last
key of the result. The run refuses (non-zero exit, no result) without a
TPU, with fewer chips than the cell asks for, on a device kind missing
from ``chipbench/peaks.json``, and outside a checkout of the program.
"""
from __future__ import annotations

import argparse
import gc
import json
import logging
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from chipbench import manifest, traffic

ROOT = manifest.PKG_ROOT
DRAIN_S = 60.0          # the longest wait past the close for a request due
MIN_TOKENS = 16         # tokens a request due in the window is served
TRACE_S = 5.0           # traced stretch of a --trace 1 run
QBLK = 512              # query rows per block of the reference


class Refused(Exception):
    """The run cannot measure here; ``code`` is the exit status."""

    def __init__(self, msg: str, code: int = 2):
        super().__init__(msg)
        self.code = code


@dataclass
class Req:
    """One request as the load generator saw it (perf_counter seconds)."""
    item: traffic.Item
    due: float
    in_window: bool = False
    rid: int = -1
    submit: Optional[float] = None
    times: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    slot: int = -1
    rank_refs: List = field(default_factory=list)
    ranks: Optional[np.ndarray] = None
    handle: object = None
    done: Optional[bool] = None

    @property
    def first(self) -> Optional[float]:
        return self.times[0] if self.times else None

    @property
    def finished(self) -> bool:
        """Served to the end (not cancelled)."""
        if self.done is not None:
            return self.done
        h = self.handle
        return h is not None and h.done and not h.cancelled

    @property
    def served_enough(self) -> bool:
        return self.finished or len(self.times) >= min(self.item.max_new,
                                                       MIN_TOKENS)

    def release(self, rank_on: bool) -> None:
        """Keep what the checks read; drop the references into the
        engine so that its device memory can be freed."""
        import jax
        self.done = self.finished
        self.ranks = (np.array([int(jax.device_get(a)[self.slot])
                                for a in self.rank_refs], np.int32)
                      if rank_on and self.slot >= 0
                      else np.zeros((0,), np.int32))
        self.rank_refs, self.handle = [], None


@dataclass
class Record:
    """Everything a per-layer reader may read about one run."""
    cell: str
    spec: object
    peaks: Optional[Dict]
    seconds: float
    t0: float
    t1: float
    requests: List[Req]
    counters0: Dict
    counters1: Dict
    spans: List[Dict] = field(default_factory=list)
    trace: object = None


def load_peaks(kind: str) -> Dict:
    table = json.loads((ROOT / "chipbench" / "peaks.json").read_text())
    if kind not in table:
        raise Refused(f"device kind {kind!r} is not in chipbench/peaks.json",
                      code=4)
    return table[kind]


def check_devices(jax, chips: int) -> Dict:
    devs = jax.devices()
    if not devs or devs[0].platform != "tpu":
        raise Refused(f"no TPU visible (JAX platform "
                      f"{devs[0].platform if devs else None!r})", code=3)
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees {len(devs)}",
                      code=3)
    return load_peaks(devs[0].device_kind)


def model_config(spec):
    """The program's ModelConfig for a configuration file."""
    from repro.configs import get_config
    from repro.configs.base import RankConfig
    cfg = get_config(spec.repro_config)
    return cfg.with_(
        num_layers=spec.layers, d_model=spec.d_model, num_heads=spec.heads,
        num_kv_heads=spec.kv_heads, head_dim=spec.head_dim, d_ff=spec.d_ff,
        vocab_size=spec.vocab, rope_theta=spec.rope_theta,
        rms_eps=spec.rms_eps, dtype=spec.dtype, param_dtype=spec.dtype,
        tie_embeddings=False, qkv_bias=False,
        max_seq_len=max(cfg.max_seq_len, spec.max_len),
        rank=RankConfig(mode=spec.rank_mode, rank_grid=spec.rank_grid,
                        segment_len=spec.segment_len))


class CompileCount(logging.Handler):
    """Counts XLA compilations (``jax_log_compiles`` records)."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.count = 0

    def emit(self, record):
        if record.getMessage().startswith("Compiling "):
            self.count += 1


def pct(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def _callback(req: Req, core, seg: int, rank_on: bool) -> Callable:
    def on_token(idx: int, tok: int) -> None:
        req.times.append(time.perf_counter())
        req.tokens.append(tok)
        if rank_on and idx >= 1 and (idx - 1) % seg == 0:
            # the step that produced token idx ran decode step idx - 1,
            # a segment boundary: its rank record is the newest one
            if req.slot < 0:
                for i, st in enumerate(core.sched.slots):
                    if st.req is not None and st.req.rid == req.rid:
                        req.slot = i
            req.rank_refs.append(core.rank_history[-1][1])
    return on_token


def _submit(front, req: Req, core, seg: int, rank_on: bool) -> None:
    from repro.serve.api import SamplingParams
    it = req.item
    req.submit = time.perf_counter()
    h = front.submit(it.prompt, SamplingParams(max_new=it.max_new),
                     on_token=_callback(req, core, seg, rank_on))
    req.rid, req.handle = h.rid, h


def _sleep_until(t: float) -> None:
    while True:
        dt = t - time.perf_counter()
        if dt <= 0:
            return
        time.sleep(min(dt, 0.05) if dt > 0.002 else 0.0002)


def serve_open(front, core, sched: traffic.Schedule, seconds: float,
               spec, on_window: Callable[[float], None]):
    """Arrivals on the schedule's wall clock; returns (requests, t0, t1).
    Arrivals go on past the close until every request due in the window
    has finished or been served ``MIN_TOKENS`` tokens (at most
    ``DRAIN_S``), so that the load stays as it was while they are."""
    rank_on = spec.rank_mode != "off"
    t_start = time.perf_counter()
    t0 = t_start + sched.ramp_s
    t1 = t0 + seconds
    reqs = [Req(it, t0 + it.due_s, in_window=0.0 <= it.due_s < seconds)
            for it in sched.items]
    window = [r for r in reqs if r.in_window]

    def window_done() -> bool:
        return all(w.served_enough for w in window) or \
            time.perf_counter() > t1 + DRAIN_S

    opened = False
    for r in reqs:
        if not opened and r.due >= t0:
            _sleep_until(t0)
            on_window(t0)
            opened = True
        if r.due >= t1:
            # past the close: arrivals go on only while a request due in
            # the window is still being served
            while time.perf_counter() < r.due and not window_done():
                time.sleep(0.005)
            if window_done():
                break
        _sleep_until(r.due)
        _submit(front, r, core, spec.segment_len, rank_on)
    if not opened:
        _sleep_until(t0)
        on_window(t0)
    while not window_done():
        time.sleep(0.01)
    return reqs, t0, t1


def serve_closed(front, core, sched: traffic.Schedule, seconds: float,
                 spec, on_window: Callable[[float], None]):
    """Every session's first turn goes in at once; the window opens once
    each has its first token (its context is built) and lasts
    ``seconds``; a session's next turn goes in when its last completes."""
    rank_on = spec.rank_mode != "off"
    turns: Dict[int, List[traffic.Item]] = {}
    for it in sched.items:
        turns.setdefault(it.session, []).append(it)
    reqs: List[Req] = []
    live: Dict[int, Req] = {}
    for s, its in turns.items():
        r = Req(its[0], time.perf_counter(), in_window=True)
        _submit(front, r, core, spec.segment_len, rank_on)
        reqs.append(r)
        live[s] = r
    while not all(r.times for r in live.values()):
        if time.perf_counter() - reqs[0].due > 1200:
            raise RuntimeError("long contexts were not built in 1200 s")
        time.sleep(0.005)
    t0 = time.perf_counter()
    on_window(t0)
    t1 = t0 + seconds
    while time.perf_counter() < t1:
        for s, r in list(live.items()):
            if r.finished and r.item.turn + 1 < len(turns[s]):
                nxt = Req(turns[s][r.item.turn + 1], time.perf_counter(),
                          in_window=True)
                _submit(front, nxt, core, spec.segment_len, rank_on)
                reqs.append(nxt)
                live[s] = nxt
        time.sleep(0.005)
    return reqs, t0, t1


def warm_eager(core, sched: traffic.Schedule) -> None:
    """Compile, before the window, the small eager programs the engine
    runs per step and per request at the sizes this traffic uses: the
    streamed token fetch, the output fetch at eviction (one per output
    length) and the prompt staging."""
    import jax
    import jax.numpy as jnp
    np.asarray(core.tokens[:, 0])
    for n in sorted({it.max_new for it in sched.items}):
        jax.block_until_ready(core.out_buf[0, :n])
    buf = jnp.zeros((core.cache.max_len,), jnp.int32)
    jax.block_until_ready(core.prompt_buf.at[0].set(buf))


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def sample_for_check(reqs: List[Req], n: int, seed: int) -> List[Req]:
    """``n`` served requests drawn from the seed, the one with the most
    served tokens always among them. Open loops draw from requests that
    finished; closed loops from every stream (cancelled at the close)."""
    pool = [r for r in reqs if r.finished] or [r for r in reqs
                                              if len(r.tokens) >= 2]
    pool.sort(key=lambda r: r.rid)
    if not pool:
        return []
    longest = max(pool, key=lambda r: len(r.tokens))
    rest = [r for r in pool if r is not longest]
    rng = np.random.default_rng(seed + 1)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def check_requests(spec, params, sample: List[Req], t0: int,
                   controls: Sequence[str] = ()):
    """Widest reference gap of the served tokens over the sample, of the
    tokens each control precision puts first at the same positions, and
    the rank-record faults. ``t0``: where every prompt ends in the
    reference's layout (the mix's longest prompt, rounded up to the query
    block), fixed per cell so the reference compiles once."""
    from chipbench import model
    seg = spec.segment_len
    need = max(1, max(-(-(len(r.tokens) - 1) // seg) for r in sample))
    n_seg = 1 << (need - 1).bit_length()
    worst = {None: 0.0, **{c: 0.0 for c in controls}}
    n_tok, bad_rank = 0, 0
    for r in sample:
        served = np.asarray(r.tokens, np.int32)
        want = -(-(len(served) - 1) // seg) if spec.rank_mode != "off" else 0
        if len(r.ranks) < want or not set(r.ranks.tolist()) <= set(
                spec.rank_grid):
            bad_rank += 1
        x = model.final_hidden(spec, params, r.item.prompt, served, r.ranks,
                               t0=t0, n_seg=n_seg, qblk=QBLK)
        g, _ = model.gaps(spec, params, x, served, t0=t0)
        worst[None] = max(worst[None], float(g.max()))
        for c in controls:
            xc = model.final_hidden(spec, params, r.item.prompt, served,
                                    r.ranks, t0=t0, n_seg=n_seg, qblk=QBLK,
                                    lo=c)
            _, gc_ = model.gaps(spec, params, x, served, t0=t0, x_ctl=xc,
                                lo=c)
            worst[c] = max(worst[c], float(gc_.max()))
            del xc
        n_tok += len(served)
        del x
    return {"gap": worst.pop(None), "control_gaps": worst, "tokens": n_tok,
            "bad_rank_records": bad_rank}


def prompt_end(sched: traffic.Schedule) -> int:
    longest = max(len(it.prompt) for it in sched.items)
    return -(-longest // QBLK) * QBLK


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def end_to_end(rec: Record, setup_s: float) -> Dict[str, float]:
    """Every end-to-end metric of the harness for this run; the caller
    keeps the cell's own."""
    win = [r for r in rec.requests if r.in_window]
    out = {"setup_s": setup_s}
    limit = rec.t1 + DRAIN_S
    ttft = [(r.first if r.first is not None else limit) - r.due for r in win]
    if ttft:
        out["ttft_p50_s"] = pct(ttft, 50)
        out["ttft_p60_s"] = pct(ttft, 60)
    tpot = [(r.times[-1] - r.times[0]) / (len(r.times) - 1) * 1e3
            for r in win if len(r.times) >= 2]
    if tpot:
        out["tpot_p50_ms"] = pct(tpot, 50)
        out["tpot_p60_ms"] = pct(tpot, 60)
    n_tok = sum(int(np.sum((np.asarray(r.times) >= rec.t0)
                           & (np.asarray(r.times) < rec.t1)))
                for r in rec.requests)
    out["tokens_per_s"] = n_tok / rec.seconds
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, require_tpu: bool = True,
             fault: Optional[Callable] = None, controls: Sequence[str] = (),
             log=sys.stderr) -> Dict:
    """One run of one cell; returns the result object. ``require_tpu``
    False (tests) skips the look for a chip; ``fault`` may break the
    engine before it compiles. With ``controls`` (precisions) the check
    also reads the reference in each; the first then takes the program's
    place in the comparison that decides ``correct``."""
    t_start = time.perf_counter()
    bench = manifest.Bench.load(root)
    cell = bench.cell(workload)
    from chipbench import model
    spec = model.load_spec(bench.config_path(cell.config))
    mix = traffic.load_mix(bench.mix_path(cell.traffic))
    src = ROOT / "src"
    if not (src / "repro" / "__init__.py").is_file():
        raise Refused(f"no program at {src / 'repro'}: run from a checkout",
                      code=2)
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import jax
    peaks = None
    if require_tpu:
        peaks = check_devices(jax, cell.chips)
        # a fixed directory in the checkout: the path is part of the key
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return _run(jax, bench, cell, spec, mix, seed, seconds, trace,
                t_start, peaks, fault, controls, log)


def _run(jax, bench, cell, spec, mix, seed, seconds, trace, t_start, peaks,
         fault, controls, log) -> Dict:
    from chipbench import model
    from repro.core.drrl import init_agent
    from repro.serve.api import Engine, EngineConfig
    from repro.serve.frontend import FrontEnd
    dev = jax.devices()[0]
    cfg = model_config(spec)
    sched = traffic.generate(mix, seed, seconds, spec.vocab,
                             max_len=spec.max_len,
                             max_new_cap=spec.max_new_cap)
    params = model.init_params(spec, seed)
    policy = None
    if spec.rank_mode in ("drrl", "learned"):
        policy = init_agent(jax.random.fold_in(model.seed_key(seed), 1),
                            cfg.rank, cfg.d_model)
    jax.block_until_ready(params)
    eng = Engine(cfg, params, policy, config=EngineConfig(
        n_slots=spec.n_slots, max_len=spec.max_len,
        max_new_cap=spec.max_new_cap, obs_trace=bool(trace)))
    if fault is not None:
        fault(eng)
    front = FrontEnd(eng)
    core = eng.core
    warm_eager(core, sched)

    counter = CompileCount()
    jax_log = logging.getLogger("jax")
    marks: Dict[str, object] = {}
    prof: Dict[str, object] = {}
    occ: List = []

    def on_window(t0: float) -> None:
        marks["setup_s"] = t0 - t_start
        marks["counters0"] = dict(eng.stats)
        jax.config.update("jax_log_compiles", True)
        jax_log.addHandler(counter)
        marks["occupancy"] = _start_occupancy(core, t0 + seconds, occ)
        if trace:
            prof["thread"] = _start_profiler(t0, seconds, prof)

    serve = serve_open if sched.loop == "open" else serve_closed
    try:
        reqs, t0, t1 = serve(front, core, sched, seconds, spec, on_window)
        counters1 = dict(eng.stats)
    finally:
        jax_log.removeHandler(counter)
        jax.config.update("jax_log_compiles", False)
        for th in (prof.get("thread"), marks.get("occupancy")):
            if th is not None:
                th.join()
    compiles = counter.count
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    for r in reqs:
        if r.handle is not None and not r.handle.done:
            r.handle.cancel()
    front.shutdown()
    for r in reqs:
        r.release(spec.rank_mode != "off")
    spans = _spans(eng) if trace else []
    rec = Record(cell.name, spec, peaks, seconds, t0, t1, reqs,
                 marks["counters0"], counters1, spans,
                 reduce_profile(prof, spans) if trace else None)
    del front, eng, core
    gc.collect()

    t_check = time.perf_counter()
    sample = sample_for_check(reqs, int(mix.get("check_requests", 4)), seed)
    chk = check_requests(spec, params, sample, prompt_end(sched), controls)
    check_s = time.perf_counter() - t_check
    # the control, where there is one, stands in the program's place
    gap = chk["control_gaps"][controls[0]] if controls else chk["gap"]
    win = [r for r in reqs if r.in_window]
    failed = sum(1 for r in win if r.first is None)
    short = sum(1 for r in sample if len(r.tokens) != r.item.max_new
                and r.finished)
    checks = {
        "logit_gap": {"value": gap, "limit": spec.gap_limit},
        "tokens_checked": {"value": chk["tokens"], "limit": 1},
        "bad_rank_records": {"value": chk["bad_rank_records"], "limit": 0},
        "short_outputs": {"value": short, "limit": 0},
    }
    correct = (gap <= spec.gap_limit and chk["tokens"] >= 1
               and chk["bad_rank_records"] == 0 and short == 0)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(mem)}
    result: Dict = {"correct": bool(correct), "attempted": len(win),
                    "failed": failed}
    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = bench.reader(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        result["metrics"] = metrics
        tr = rec.trace
        device["busy_s"] = float(tr.busy_s) if tr else 0.0
        device["window_s"] = float(tr.window_s) if tr else 0.0
        result["device"] = device
        if tr is not None:
            result["breakdown"] = {
                "device_ops": [[n, float(s)] for n, s in tr.top_ops],
                "idle_gaps": [[n, float(s)] for n, s in tr.idle_gaps]}
    else:
        allm = end_to_end(rec, marks["setup_s"])
        result["metrics"] = {m["name"]: {"value": float(allm[m["name"]]),
                                         "unit": m["unit"]}
                             for m in cell.end_to_end if m["name"] in allm}
        result["device"] = device
    result["info"] = {"compiles_in_window": compiles,
                      "requests_submitted": sum(1 for r in reqs
                                                if r.submit is not None),
                      "program_gap": chk["gap"],
                      "control_gaps": chk["control_gaps"],
                      "check_s": check_s, **occupancy(occ, spec),
                      "sample": [[r.rid, len(r.item.prompt), len(r.tokens)]
                                 for r in sample]}
    result["checks"] = checks
    for k, v in checks.items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=log)
    return result


def _start_occupancy(core, t1: float, out: List) -> threading.Thread:
    """Four times a second until ``t1``: the slots in use and the cache
    positions they hold (read from the scheduler without its lock, as a
    sample)."""
    def body():
        t = time.perf_counter()
        while t < t1:
            live = [st for st in core.sched.slots if st.active]
            out.append((len(live), sum(st.prefilled + st.n_out
                                       for st in live)))
            t += 0.25
            _sleep_until(min(t, t1))
    th = threading.Thread(target=body, name="chipbench-occupancy",
                          daemon=True)
    th.start()
    return th


def occupancy(samples: List, spec) -> Dict[str, float]:
    """Mean slots in use and mean share of the K/V pool's positions that
    hold a live token, over the window."""
    if not samples:
        return {}
    a = np.asarray(samples, np.float64)
    return {"slots_live_mean": float(a[:, 0].mean()),
            "kv_live_share": float(a[:, 1].mean()
                                   / (spec.n_slots * spec.max_len))}


def _start_profiler(t0: float, seconds: float, prof: Dict) -> threading.Thread:
    """Profile ``TRACE_S`` seconds from the middle of the window, on a
    thread of its own so the load generator keeps its schedule."""
    import jax

    def body():
        span = min(TRACE_S, seconds / 3)
        start = t0 + max(0.0, (seconds - span) / 2)
        _sleep_until(start)
        log_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # host Python calls: off
        opts.host_tracer_level = 1         # annotations only
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("chipbench_anchor"):
            anchor = time.perf_counter()
        w0 = anchor
        _sleep_until(w0 + span)
        w1 = time.perf_counter()
        jax.profiler.stop_trace()
        prof["raw"] = (log_dir, anchor, (w0, w1))
    th = threading.Thread(target=body, name="chipbench-profiler", daemon=True)
    th.start()
    return th


def _spans(eng) -> List[Dict]:
    """The engine's ``repro.obs`` span events with ``abs_s`` (perf_counter
    seconds) added."""
    tr = eng.obs.tracer
    if tr is None:
        return []
    base = tr._t0
    out = []
    for ev in tr.events:
        e = dict(ev)
        e["abs_s"] = base + ev["ts"] * 1e-6
        out.append(e)
    return out


def reduce_profile(prof: Dict, spans: List[Dict]):
    import shutil
    from chipbench import trace as tr
    if "raw" not in prof:
        return None
    log_dir, anchor, window = prof["raw"]
    phases = [(e["name"], e["abs_s"], e["abs_s"] + e["dur"] * 1e-6)
              for e in spans if e.get("cat") == "phase"]
    try:
        return tr.reduce_trace(tr.find_xplane(log_dir), anchor, window,
                               phases)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except Refused as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return e.code
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
