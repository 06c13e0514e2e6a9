"""Runtime sanitizer lane: transfer-guard + compile-count checks on the
serving engine.

Two dynamic invariants the static pass (repro.analysis.rules) cannot
prove are enforced here by actually running the serve smoke workload:

* **transfer guard** — once warm, every fused step executes under
  ``jax.transfer_guard("disallow")``: any implicit host<->device copy
  inside the step dispatch (a stray ``np.asarray`` on a traced output, a
  numpy arg silently uploaded per step) raises immediately instead of
  costing a hidden sync per token.  The guard wraps the compiled step
  callables only — the engine's sanctioned per-step accept/emission
  fetch (``jax.device_get``, an *explicit* transfer) stays legal, and
  control-plane phases (admission, warmup, reset) stay unguarded.

* **compile counting** — with ``jax.log_compiles``, every new XLA
  executable logs one ``"Compiling ..."`` record on the ``jax`` logger.
  The warm phase (construction + warmup + first full run) may compile
  freely; the steady phase then replays a *shape-identical* workload —
  same (prompt_len, max_new) multiset, different token content, seeds
  and sampling mixes — through the reset engine and asserts **zero** new
  executables.  Rank switches, draft/verify steps and mixed
  greedy/top-k/top-p batches must all ride the executables warmup
  already built; a recompile here is a latency cliff in production.

Scenarios:

* ``mixed_sampling`` — adaptive ranks, chunked prefill, greedy + top-k +
  nucleus rows in the same batch;
* ``speculative``   — self-speculative draft/verify with adaptive ranks
  (rank decisions fire mid-stream on both phases);
* ``learned_policy`` — ``mode="learned"``: the policy-net rank decision
  runs device-resident inside the jitted decide executable (untrained
  params — the check is about executables, not reward).

Run::

    PYTHONPATH=src python -m repro.analysis.sanitizer [--json]

Exit status is non-zero if any scenario compiles in steady state or
trips the transfer guard.  benchmarks/serve_bench.py runs this module as
a subprocess and lands the counts in BENCH_serve.json under
``compile_guard``, where benchmarks/check_bench.py gates them exactly.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import logging
import sys

import numpy as np

__all__ = ["CompileCounter", "guard_steps", "run_scenario", "main"]


class CompileCounter(logging.Handler):
    """Count new-executable compilations via the ``jax`` logger.

    Under ``jax.log_compiles(True)`` each cache-miss compilation emits a
    WARNING record whose message starts with ``"Compiling "`` (cache
    hits are silent), so the handler's count is exactly the number of
    new executables built while attached.
    """

    def __init__(self) -> None:
        super().__init__(level=logging.DEBUG)
        self.count = 0
        self.messages: list[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.count += 1
            self.messages.append(msg.split("\n", 1)[0][:200])

    @contextlib.contextmanager
    def attached(self):
        import jax

        # log_compiles raises the relevant jax loggers to emit the
        # per-executable WARNING records; we only listen, never change
        # levels (raising "jax" to DEBUG floods stderr via jax's own
        # handler)
        logger = logging.getLogger("jax")
        logger.addHandler(self)
        try:
            with jax.log_compiles(True):
                yield self
        finally:
            logger.removeHandler(self)


def guard_steps(eng) -> None:
    """Wrap the engine's fused-step callables in a disallow transfer
    guard.  Arguments are evaluated at the call site — *outside* the
    guard — so only the dispatch + execution of the compiled step is
    policed, which is exactly the per-token hot path."""
    import jax

    def _guarded(fn):
        def wrapper(*args, **kwargs):
            with jax.transfer_guard("disallow"):
                return fn(*args, **kwargs)
        return wrapper

    for name in ("_step", "_step_mixed", "_step_spec"):
        fn = getattr(eng, name, None)
        if fn is not None:
            setattr(eng, name, _guarded(fn))


def _workload(n_requests: int, max_new: int, *, seed: int,
              sampling: bool) -> list[dict]:
    """Mixed prompt lengths; shape layout is seed-independent so two
    workloads with different seeds are executable-identical."""
    rnd = np.random.default_rng(seed)
    lens = [8, 12, 16, 24, 12, 16, 8, 24][:n_requests]
    out = []
    for i, ln in enumerate(lens):
        req = dict(rid=i, tokens=rnd.integers(0, 256, ln).astype(np.int32),
                   max_new=max_new, arrival=2 * i)
        if sampling:
            # greedy / top-k / nucleus rows interleaved in one batch
            kind = i % 3
            if kind == 1:
                req.update(temperature=0.8, top_k=8, seed=int(seed + i))
            elif kind == 2:
                req.update(temperature=0.9, top_p=0.9, seed=int(seed + i))
        out.append(req)
    return out


def run_scenario(name: str, *, n_requests: int = 6,
                 max_new: int = 12) -> dict:
    """Warm-then-steady run of one scenario; returns the count dict."""
    import jax

    from repro.configs import get_config
    from repro.configs.base import RankConfig
    from repro.models.api import get_model
    from repro.serve import EngineConfig, Request, ServeEngine

    grid = (4, 8, 12, 16)
    mode = "learned" if name == "learned_policy" else "adaptive"
    cfg = get_config("drrl-paper", reduced=True).with_(
        rank=RankConfig(mode=mode, rank_grid=grid, segment_len=8))
    fns = get_model(cfg)
    params = fns.init(jax.random.PRNGKey(0))

    policy_params = None
    if mode == "learned":
        # untrained policy net: executable identity is decided by shapes
        # and structure, not by the weights, so an init tree is exactly
        # as compile-prone as a trained checkpoint
        from repro.core.drrl import feat_dims
        from repro.core.policy import init_policy
        policy_params = init_policy(jax.random.PRNGKey(1),
                                    feat_dims(cfg.rank), len(grid))

    # the observability scenario is the mixed-sampling workload with
    # metrics + span/phase tracing ON: it must add ZERO new executables
    # and ZERO unsanctioned transfers relative to a bare steady loop —
    # the repro.obs contract that hooks are pure host Python
    sampling = name in ("mixed_sampling", "observability")
    kwargs = dict(n_slots=4, max_len=64, page_size=16, segment_len=8,
                  max_new_cap=max_new, prefill_chunk=8, sampling=sampling)
    if sampling:
        kwargs.update(nucleus=True)
    elif name == "speculative":
        kwargs.update(speculative=True, draft_k=3, draft_rank_frac=0.25)
    if name == "observability":
        kwargs.update(obs_trace=True)

    counter = CompileCounter()
    with counter.attached():
        eng = ServeEngine(cfg, params, policy_params, config=EngineConfig(
            **kwargs))

        # warm phase: compiles are free here
        for w in _workload(n_requests, max_new, seed=0, sampling=sampling):
            eng.submit(Request(**w))
        eng.warmup()
        eng.run()
        warm = counter.count

        # steady phase: same shapes, different content/seeds/sampling
        # rows — and the fused step now runs under a transfer guard
        eng.reset()
        guard_steps(eng)
        for w in _workload(n_requests, max_new, seed=7, sampling=sampling):
            eng.submit(Request(**w))
        eng.run()
        if name == "observability":
            # the export/read side must be as quiet as the hooks: render
            # every exporter inside the counted steady region (the one
            # device read — rank_telemetry's batched veto fetch — is a
            # plain device_get, never a compile)
            eng.obs.snapshot()
            eng.obs.prometheus()
            eng.obs.chrome_trace()
            eng.obs.rank_telemetry(eng)
        steady = counter.count - warm

    return {
        "scenario": name,
        "warm_executables": warm,
        "steady_new_executables": steady,
        "transfer_guard": "disallow",
        "ok": steady == 0,
        "steady_compiles": counter.messages[warm:],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="serve-engine runtime sanitizer: transfer guard + "
                    "zero-steady-state-compile check")
    ap.add_argument("--json", action="store_true",
                    help="emit the result dict as JSON on stdout")
    ap.add_argument("--scenario",
                    choices=["mixed_sampling", "speculative",
                             "learned_policy", "observability"],
                    action="append",
                    help="run only the named scenario(s); default all")
    args = ap.parse_args(argv)

    scenarios = args.scenario or ["mixed_sampling", "speculative",
                                  "learned_policy", "observability"]
    results = []
    failed = False
    for name in scenarios:
        try:
            res = run_scenario(name)
        except Exception as e:  # transfer guard raises mid-step
            res = {"scenario": name, "ok": False, "error": repr(e)}
        results.append(res)
        failed = failed or not res["ok"]

    import jax
    out = {"ok": not failed, "platform": jax.default_backend(),
           "scenarios": results}
    if args.json:
        print(json.dumps(out, indent=2))
    else:
        for r in results:
            status = "ok" if r["ok"] else "FAIL"
            detail = (f"warm {r.get('warm_executables', '?')} executables, "
                      f"steady +{r.get('steady_new_executables', '?')}"
                      if "error" not in r else r["error"])
            print(f"{r['scenario']:16s} {status}  {detail}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
