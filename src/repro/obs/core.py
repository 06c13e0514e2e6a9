"""Observability facade: one object per engine bundling the metrics
registry shard, the optional span tracer, and the flight recorder.

The engine calls the ``on_*`` hooks from its step loop with values it
**already holds on host** (slot ids, host token counts, wall-clock
deltas): every hook is pure host Python — dict lookups, int/float
adds, deque appends — with zero jax calls, so observability can stay ON
in steady state without adding device syncs or executables (the
sanitizer's ``observability`` scenario runs the steady loop with
tracing enabled under ``jax.transfer_guard("disallow")`` and a compile
counter to pin exactly that).

Rank values are the one place device values are involved, and they are
fetched **at read time only**. The jitted ``decide`` returns each
decision's chosen rank and Eq. 9 veto flag on the device; the engine
banks them unfetched and adds them into the counters
``serve.rank.chosen_sum`` and ``serve.rank.vetoes`` (its stats
``rank_chosen_sum`` / ``rank_vetoes``) in one batched ``device_get``
when those are read, through :meth:`snapshot`, :meth:`prometheus` or the
stats view. :meth:`Observability.rank_telemetry` derives the kept-rank
series, switch counts and factor-read bytes/token from the engine's
``rank_history`` (device arrays the loop already keeps, appended
without synchronisation). The fused loop never gains a host sync
(invariant R1) no matter which observability features are enabled.
"""
from __future__ import annotations

import itertools
from typing import Any, Callable, Dict, List, Optional

from repro.obs.flight import FlightRecorder
from repro.obs.metrics import (DEFAULT_COUNT_BUCKETS, MetricsRegistry,
                               StatsView)
from repro.obs.tracing import PHASES, SpanTracer, StepPhases, Stopwatch

__all__ = ["Observability", "Stopwatch"]

_ENGINE_SEQ = itertools.count()

# the engine's rank stats -> the registry counters that back them
RANK_STATS = {"rank_chosen_sum": "serve.rank.chosen_sum",
              "rank_vetoes": "serve.rank.vetoes"}


class Observability:
    """Per-engine observability bundle. Always constructed (the registry,
    the per-phase step seconds and the flight ring are cheap and always
    on); span/phase tracing is opt-in via ``trace=True`` because it
    allocates an event per step phase and per request milestone."""

    def __init__(self, *, trace: bool = False, trace_capacity: int = 200_000,
                 flight_dir: Optional[str] = None,
                 flight_capacity: int = 256,
                 engine_id: Optional[int] = None):
        self.engine_id = (next(_ENGINE_SEQ) if engine_id is None
                          else engine_id)
        self.registry = MetricsRegistry()
        self.tracer = (SpanTracer(pid=self.engine_id,
                                  capacity=trace_capacity)
                       if trace else None)
        self.flight = FlightRecorder(flight_capacity, flight_dir,
                                     name=f"engine{self.engine_id}")
        r = self.registry
        # histograms: TTFT and per-token decode latency feed the bench
        # percentiles; accept-run lengths are small discrete counts
        self.ttft_hist = r.histogram("serve.ttft_s")
        self.latency_hist = r.histogram("serve.token_latency_s")
        self.accept_hist = r.histogram("serve.accept_len",
                                       bounds=DEFAULT_COUNT_BUCKETS)
        # seconds spent in each step phase, summed over steps; also in
        # the engine's ``stats`` view as ``phase.<p>_s``
        self._phase_s = {p: r.counter(f"serve.phase.{p}_s", init=0.0)
                         for p in PHASES}
        # request + rank control-plane counters (the per-step token/stat
        # counters live behind the engine's StatsView — same registry)
        self._c_admitted = r.counter("requests.admitted")
        self._c_finished = r.counter("requests.finished")
        self._c_cancelled = r.counter("requests.cancelled")
        self._c_decisions = r.counter("rank.decisions")
        self._c_forced = r.counter("rank.forced_decides")
        self._c_drift = r.counter("rank.drift_triggers")
        # filled from the engine's banked decisions when read (see the
        # module docstring); in the engine's stats under RANK_STATS' keys
        self._c_rank_chosen = r.counter("serve.rank.chosen_sum")
        self._c_rank_vetoes = r.counter("serve.rank.vetoes")
        self._refresh = None
        self._g_queue = r.gauge("queue.depth")
        self._g_live = r.gauge("slots.live")
        self._g_kv = r.gauge("kv.positions_live")
        self._g_prefix_nodes = r.gauge("prefix.nodes")
        self._g_prefix_pages = r.gauge("prefix.pages")

    # -- engine wiring ----------------------------------------------------

    def stats_view(self, init: Dict[str, Any], gauges=("eff_draft_k",),
                   refresh: Optional[Callable[[], None]] = None
                   ) -> StatsView:
        """The engine's legacy ``stats`` surface as a registry view (and
        the reset path: re-binding zeroes the backing metrics), with the
        step-phase seconds ``phase.<p>_s`` and the rank counters
        ``rank_chosen_sum`` / ``rank_vetoes`` added. ``refresh`` adds
        what the engine has banked into the rank counters
        (:meth:`on_ranks_fetched`); it runs before they are read, here
        or by the exporters."""
        init = {**init, **{f"phase.{p}_s": 0.0 for p in PHASES},
                **{k: 0 for k in RANK_STATS}}
        self._refresh = refresh
        return StatsView(self.registry, init, prefix="serve", gauges=gauges,
                         names=RANK_STATS, refresh=refresh, lazy=RANK_STATS)

    def on_ranks_fetched(self, chosen_sum: int, vetoes: int) -> None:
        """Banked decisions, fetched: the ranks they chose, summed, and
        how many the Eq. 9 bound vetoed."""
        self._c_rank_chosen.inc(chosen_sum)
        self._c_rank_vetoes.inc(vetoes)

    def reset_run(self) -> None:
        """Engine reset: clear the per-run trace buffer (the flight ring
        deliberately survives — it exists for post-mortems)."""
        if self.tracer is not None:
            self.tracer.clear()

    def step_phases(self, step: int) -> StepPhases:
        """Phase recorder for one step: two clock reads, one add and one
        profiler annotation per phase, plus an X event when tracing."""
        return StepPhases(self._phase_s, step, self.tracer)

    # -- hooks (host values only; called from the step loop) --------------

    def on_admit(self, rid: int, slot: int, prompt_len: int, *,
                 reused: int = 0) -> None:
        self._c_admitted.inc()
        self.flight.record("admit", rid=rid, slot=slot,
                           prompt_len=prompt_len, reused=reused)
        if self.tracer is not None:
            self.tracer.async_begin("request", rid,
                                    args={"rid": rid, "slot": slot,
                                          "prompt_len": prompt_len,
                                          "prefix_reused": reused})

    def on_first_token(self, rid: int, slot: int, ttft_s: float) -> None:
        self.ttft_hist.observe(ttft_s)
        if self.tracer is not None:
            self.tracer.instant("first_token", tid=slot, cat="request",
                                args={"rid": rid,
                                      "ttft_ms": ttft_s * 1e3})

    def on_finish(self, rid: int, slot: int, n_out: int,
                  reason: str) -> None:
        (self._c_cancelled if reason == "cancel"
         else self._c_finished).inc()
        self.flight.record("finish", rid=rid, slot=slot, n_out=n_out,
                           reason=reason)
        if self.tracer is not None:
            self.tracer.async_end("request", rid,
                                  args={"rid": rid, "n_out": n_out,
                                        "reason": reason})

    def on_decide(self, slot: int, seg_t: int, *,
                  forced: bool = False) -> None:
        self._c_decisions.inc()
        if forced:
            self._c_forced.inc()
        self.flight.record("decide", slot=slot, seg_t=seg_t,
                           forced=forced)
        if self.tracer is not None:
            self.tracer.instant("rank_decide", tid=slot, cat="rank",
                                args={"slot": slot, "seg_t": seg_t,
                                      "forced": forced})

    def on_step(self, *, queued: int, live: int, kv_positions: int) -> None:
        """End of a step: requests waiting, slots in use and the cache
        positions they hold (prompt tokens prefilled + tokens out)."""
        self._g_queue.set(queued)
        self._g_live.set(live)
        self._g_kv.set(kv_positions)

    def on_drift(self, slot: int, drift: float) -> None:
        self._c_drift.inc()
        self.flight.record("drift", slot=slot, drift=drift)
        if self.tracer is not None:
            self.tracer.instant("basis_drift", tid=slot, cat="rank",
                                args={"slot": slot, "drift": drift})

    def on_prefill_chunk(self, slot: int, rid: int, q: int,
                         prefilled: int) -> None:
        if self.tracer is not None:
            self.tracer.instant("prefill_chunk", tid=slot, cat="request",
                                args={"rid": rid, "q": q,
                                      "prefilled": prefilled})

    def on_spec_accept(self, slot: int, accepted: int,
                       drafted: int) -> None:
        self.accept_hist.observe(float(accepted))
        if self.tracer is not None:
            self.tracer.instant("spec_accept", tid=slot, cat="spec",
                                args={"slot": slot, "accepted": accepted,
                                      "drafted": drafted})

    def on_token_latency(self, dt_s: float) -> None:
        self.latency_hist.observe(dt_s)

    def set_prefix_size(self, nodes: int, pages: int) -> None:
        self._g_prefix_nodes.set(nodes)
        self._g_prefix_pages.set(pages)

    def record_event(self, kind: str, **fields: Any) -> None:
        """Free-form flight-ring event (cancellations, evictions,
        exceptions)."""
        self.flight.record(kind, **fields)

    # -- exporters (read side; any thread) --------------------------------

    def snapshot(self) -> Dict[str, Any]:
        """JSON-ready point-in-time export of this engine's shard."""
        if self._refresh is not None:
            self._refresh()
        return {
            "engine_id": self.engine_id,
            "metrics": self.registry.snapshot(),
            "trace": {
                "enabled": self.tracer is not None,
                "events": len(self.tracer.events) if self.tracer else 0,
                "dropped": self.tracer.dropped if self.tracer else 0,
            },
            "flight": {
                "events": len(self.flight.events),
                "recorded": self.flight.n_recorded,
                "dumps": self.flight.n_dumps,
            },
        }

    def prometheus(self, namespace: str = "repro") -> str:
        if self._refresh is not None:
            self._refresh()
        return self.registry.prometheus_text(namespace)

    def chrome_trace(self) -> Dict[str, Any]:
        """Chrome trace-event document (empty when tracing is off)."""
        if self.tracer is None:
            return {"traceEvents": [], "displayTimeUnit": "ms",
                    "otherData": {"dropped_events": 0}}
        return self.tracer.chrome_trace(
            metadata={"engine_id": self.engine_id})

    def flight_dump(self, reason: str, *,
                    error: Optional[BaseException] = None,
                    path: Optional[str] = None) -> Optional[str]:
        """Dump the flight ring + a registry snapshot. Host-only — safe
        from exception handlers on any thread."""
        return self.flight.dump(reason, metrics=self.registry.snapshot(),
                                error=error, path=path)

    def rank_telemetry(self, engine) -> Dict[str, Any]:
        """Export-time rank report for ``engine`` (a ServeEngine): the
        kept-rank time series, switch counts, Eq. 9 veto fires and
        factor-read bytes/token, derived from device state the loop
        already banked (``rank_history`` and the unfetched per-decision
        values behind ``stats['rank_vetoes']``). The only host transfers
        happen HERE, at read time — never inside the fused loop."""
        import numpy as np

        series = engine.ranks_per_step()          # host: -1 = off/dead
        switches = 0
        mean_rank = 0.0
        if series:
            mat = np.stack(series)                # (steps, n_slots)
            live = mat >= 0
            mean_rank = float(mat[live].mean()) if live.any() else 0.0
            for j in range(mat.shape[1]):
                col = mat[live[:, j], j]
                if col.size > 1:
                    switches += int((np.diff(col) != 0).sum())
        veto = int(engine.stats["rank_vetoes"])   # fetches what is banked
        # analytic factor-read bytes/token for currently-live slots
        # (same formula as repro.serve.traces: L * kv_len * hkv * r * 4)
        cfg = engine.cfg
        hkv = cfg.num_kv_heads
        read_bpt = []
        if series:
            last = series[-1]
            for j, r in enumerate(last):
                if r >= 0:
                    read_bpt.append(float(cfg.num_layers)
                                    * float(engine.cache.lens[j])
                                    * hkv * float(r) * 4.0)
        return {
            "steps_recorded": len(series),
            # rank is uniform across layers in this engine (the decision
            # is driven by layer-0 spectra and applied to every layer),
            # so one series per slot IS the per-layer series
            "per_layer_uniform": True,
            "kept_rank": [[int(v) for v in row] for row in series],
            "mean_kept_rank": mean_rank,
            "rank_switches": switches,
            "veto_fires": veto,
            # every decision refreshes the basis
            "basis_refreshes": self._c_decisions.value,
            "drift_triggers": self._c_drift.value,
            "decisions": self._c_decisions.value,
            "read_bytes_per_token": (float(np.mean(read_bpt))
                                     if read_bpt else 0.0),
        }
