"""Unified streaming serving API (paper §4.5.2 as a request/response
surface).

The engine-construction knobs live in one :class:`EngineConfig`
(``repro.serve.config``, shared with the core ``ServeEngine``), per-request
generation knobs in :class:`SamplingParams` (greedy by default; seeded
temperature / top-k run **in-graph** in the fused step — one executable
regardless of the mix of greedy and sampled streams), and
:meth:`Engine.submit` returns a :class:`RequestHandle` that streams
tokens incrementally (iterator or callback) and records per-request TTFT.

Prompts are admitted via **chunked prefill**
(``EngineConfig.prefill_chunk``): the prompt is consumed a fixed-size
chunk at a time *inside* the fused decode step, alongside the live decode
rows — admission never stalls decoding, and prompts of any length share
one executable. The chunk width does not change the tokens
(tests/test_serve_chunked.py).

    cfg = EngineConfig(n_slots=4, max_len=256)
    eng = Engine(model_cfg, params, config=cfg)
    h = eng.submit(prompt_ids, SamplingParams(max_new=64))
    for tok in h.tokens():          # drives eng.step() as needed
        ...
    # or: eng.run(); h.result()

The explicit step loop (``eng.step()`` / ``eng.run()``) stays available
for servers that multiplex many handles.
"""
from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from repro.configs.base import ModelConfig
from repro.serve.config import EngineConfig
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import Request


@dataclass(frozen=True)
class SamplingParams:
    """Per-request generation knobs.

    ``temperature == 0`` (the default) is greedy argmax; ``top_k == 0``
    samples the full vocabulary; ``top_p == 1`` disables the nucleus cut
    (``top_p < 1`` keeps the smallest probability-sorted set whose mass
    reaches ``top_p`` — composable with ``top_k``, applied after it, and
    requires an engine built with ``EngineConfig(nucleus=True)``).
    ``seed`` keys a per-token PRNG fold — a stream's draw sequence is a
    pure function of (seed, token index), reproducible under any
    batching/admission interleaving. Greedy, top-k and top-p streams all
    share ONE fused-step executable."""
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    max_new: int = 64
    eos_id: Optional[int] = None

    def __post_init__(self):
        if self.temperature < 0:
            raise ValueError(f"negative temperature {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"negative top_k {self.top_k}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        if self.max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {self.max_new}")


class EngineStopped(RuntimeError):
    """The engine serving a handle died or was shut down mid-stream.

    Raised from ``RequestHandle`` iterators / ``result()`` instead of
    blocking forever: a front-end stepping thread that crashed, a
    ``FrontEnd.shutdown()``, or an ``Engine.reset()`` that discarded the
    request all mark their unfinished handles stopped."""


@dataclass
class RequestHandle:
    """One submitted request: incremental tokens + completion state.

    Tokens arrive through a per-request in-order queue (``_toks`` +
    condition variable): with a background stepping thread attached
    (repro.serve.frontend.FrontEnd) consumers block on the condition,
    without one they drive ``engine.step()`` themselves — the same
    handle supports ``for tok in h.tokens()``, ``async for tok in h``,
    ``h.result()`` and the ``on_token`` callback. ``cancel()`` aborts
    the request mid-stream (slot evicted, pages released); no token is
    delivered after it returns."""
    rid: int
    prompt_len: int
    params: SamplingParams
    _engine: "Engine"
    _submit_s: float
    on_token: Optional[Callable[[int, int], None]] = None
    _toks: List[int] = field(default_factory=list)
    _result: Optional[np.ndarray] = None
    ttft_s: Optional[float] = None   # submit() -> first-token wall time
    done_s: Optional[float] = None   # submit() -> completion wall time
    replica: Optional[int] = None    # set by Router.submit
    cancelled: bool = False
    _stopped: bool = False
    _cv: threading.Condition = field(default_factory=threading.Condition)

    @property
    def done(self) -> bool:
        return self._result is not None

    def _check_stopped(self) -> None:
        if self._stopped:
            raise EngineStopped(
                f"request {self.rid}: engine stopped after "
                f"{len(self._toks)} token(s)")

    def _advance(self, i: int, poll_s: float = 0.05) -> None:
        """Block until token ``i`` exists (or the stream ended): wait on
        the delivery condition while a background thread is stepping the
        engine, drive ``engine.step()`` ourselves otherwise."""
        if self._engine.driver_alive:
            with self._cv:
                if i >= len(self._toks) and not self.done \
                        and not self._stopped:
                    # timed wait: a driver that dies without marking its
                    # handles (hard kill) still unblocks us to re-check
                    self._cv.wait(poll_s)
        else:
            self._check_stopped()
            self._engine.step()

    def tokens(self):
        """Generator of generated token ids, in order. Without a front-end
        stepping thread it drives ``engine.step()`` whenever it runs dry;
        with one it blocks until the thread delivers. Attaching a consumer
        makes the engine sync emitted token values each step (the same
        per-step sync an ``eos_id`` request already pays); handles that
        never stream keep the sync-free loop and read results at
        eviction. Raises :class:`EngineStopped` if the engine dies
        mid-stream; a ``cancel()`` ends the iteration cleanly."""
        self._engine._ensure_streaming(self)
        i = 0
        while True:
            while i < len(self._toks):
                yield self._toks[i]
                i += 1
            if self.done or self.cancelled:
                return
            self._check_stopped()
            self._advance(i)

    def __aiter__(self):
        """``async for tok in handle`` — the blocking wait runs in a
        worker thread (asyncio.to_thread) so the event loop stays free to
        consume other handles concurrently."""
        self._engine._ensure_streaming(self)
        return self._agen()

    async def _agen(self):
        import asyncio
        i = 0
        while True:
            tok = await asyncio.to_thread(self._next_blocking, i)
            if tok is None:
                return
            yield tok
            i += 1

    def _next_blocking(self, i: int) -> Optional[int]:
        """Token ``i`` (blocking), or None when the stream is over."""
        while True:
            if i < len(self._toks):
                return self._toks[i]
            if self.done or self.cancelled:
                return None
            self._check_stopped()
            self._advance(i)

    def result(self) -> np.ndarray:
        """Block until this request finishes; returns its generated ids
        (the partial output if it was cancelled). Raises
        :class:`EngineStopped` if the engine dies first."""
        while not self.done:
            self._check_stopped()
            self._advance(len(self._toks))
        return self._result

    def cancel(self) -> bool:
        """Abort this request: queued -> dropped, decoding/mid-prefill ->
        slot evicted and pages released. ``result()`` then returns the
        tokens delivered so far; iterators end cleanly. No token is
        delivered after cancel() returns. Returns False if the request
        had already finished."""
        return self._engine.cancel(self.rid)

    # -- called by Engine ------------------------------------------------

    def _feed(self, idx: int, tok: int) -> None:
        """Deliver token ``idx``. Strictly in-order: anything already
        delivered is ignored, and a gap (idx beyond the next slot) is
        refused — the engine backfills from the device buffer first, so a
        consumer never sees a garbled sequence. A cancelled or stopped
        handle refuses delivery outright."""
        if idx != len(self._toks) or self.cancelled or self._stopped:
            return
        with self._cv:
            self._toks.append(tok)
            if self.ttft_s is None and idx == 0:
                self.ttft_s = time.perf_counter() - self._submit_s
            self._cv.notify_all()
        # user callback runs outside the lock: it may block or re-enter
        if self.on_token is not None:
            self.on_token(idx, tok)

    def _finish(self, out: np.ndarray, first_tok_t: Optional[float]) -> None:
        # TTFT first: the backfill below would otherwise stamp token 0
        # with completion time on a handle that never streamed
        if first_tok_t is not None:
            with self._cv:
                if self.ttft_s is None:
                    self.ttft_s = first_tok_t - self._submit_s
        for i in range(len(self._toks), len(out)):
            self._feed(i, int(out[i]))
        with self._cv:
            self._result = np.asarray(out, np.int32)
            self.done_s = time.perf_counter() - self._submit_s
            self._cv.notify_all()

    def _mark_cancelled(self) -> None:
        """Seal the handle after an engine-level cancel: the result is
        whatever was delivered before the cut."""
        with self._cv:
            self.cancelled = True
            self._result = np.asarray(self._toks, np.int32)
            self.done_s = time.perf_counter() - self._submit_s
            self._cv.notify_all()

    def _mark_stopped(self) -> None:
        """The engine died / was reset with this request unfinished:
        unblock every consumer with EngineStopped instead of hanging."""
        if self.done:
            return
        with self._cv:
            self._stopped = True
            self._cv.notify_all()


class Engine:
    """Streaming request/response front-end over the continuous-batching
    core (:class:`repro.serve.ServeEngine`): ``submit() -> RequestHandle``,
    an explicit ``step()``/``run()`` loop, incremental token delivery and
    per-request TTFT.

    ``device`` pins the engine to one local device: its params, page
    pools and control state live there, and its steps run there (the
    :class:`repro.serve.frontend.Router` gives each replica its own).
    ``None`` leaves placement to JAX's default device."""

    def __init__(self, cfg: ModelConfig, params, policy_params=None, *,
                 config: Optional[EngineConfig] = None, device=None):
        self.config = config or EngineConfig()
        self.device = device
        if device is not None:
            import jax
            params = jax.device_put(params, device)
        with self._placed():
            self.core = ServeEngine(cfg, params, policy_params,
                                    config=self.config)
        self._handles: Dict[int, RequestHandle] = {}
        self._next_rid = 0
        self._finished_seen = 0
        self._streaming: set = set()     # rids with an attached consumer
        # background stepping thread (repro.serve.frontend.FrontEnd)
        # driving this engine, if any: handles then wait for delivery
        # instead of stepping, and reset() must strand no consumer
        self._driver = None
        # submit() may run on a non-loop thread: rid assignment, handle
        # registration and the core queue append form one critical section
        self._submit_lock = threading.Lock()
        # handles drive step() from whatever thread calls result()/
        # tokens(): whole engine iterations are serialised so concurrent
        # consumers interleave steps instead of racing the core state.
        # Reentrant: an on_token callback fires under this lock and may
        # itself drive the engine (handle.result() on a follow-up
        # request), which recurses on the same thread instead of
        # deadlocking.
        self._step_lock = threading.RLock()

    def _placed(self):
        """Context putting the arrays the core creates, and the steps it
        dispatches, on this engine's device. Thread-local: it is entered
        on whichever thread drives the engine."""
        if self.device is None:
            return contextlib.nullcontext()
        import jax
        return jax.default_device(self.device)

    # -- request plane ---------------------------------------------------

    def submit(self, prompt, params: Optional[SamplingParams] = None, *,
               arrival: int = 0,
               on_token: Optional[Callable[[int, int], None]] = None
               ) -> RequestHandle:
        """Enqueue ``prompt`` (1-D int ids). Validation is fail-fast: a
        request that could never be served (prompt + max_new beyond a
        slot's capacity, max_new beyond the engine cap, negative arrival,
        top_k beyond the compiled cap, sampling on a greedy-only engine)
        raises here instead of queueing forever.

        Thread-safe: may be called from a thread other than the one
        driving step()/run() — submission is serialised against both
        concurrent submits and the step loop's admission."""
        params = params or SamplingParams()
        with self._submit_lock:
            rid = self._next_rid
            req = Request(rid=rid, tokens=np.asarray(prompt, np.int32),
                          max_new=params.max_new, arrival=arrival,
                          eos_id=params.eos_id,
                          temperature=params.temperature,
                          top_k=params.top_k, top_p=params.top_p,
                          seed=params.seed)
            self.core.submit(req)             # may raise — rid not consumed
            self._next_rid += 1
            h = RequestHandle(rid=rid, prompt_len=len(req.tokens),
                              params=params, _engine=self,
                              _submit_s=time.perf_counter(),
                              on_token=on_token)
            self._handles[rid] = h
            if on_token is not None:
                self._streaming.add(rid)
                self.core._stream_sync = True
        drv = self._driver
        if drv is not None:
            drv.wake()                   # a parked stepping thread resumes
        return h

    @property
    def driver_alive(self) -> bool:
        """True while a background stepping thread owns the step loop."""
        drv = self._driver
        return drv is not None and drv.alive

    def cancel(self, rid: int) -> bool:
        """Abort a submitted request (see :meth:`RequestHandle.cancel`).
        Serialised against the step loop: no fused step is in flight
        while the slot is evicted, and no token is delivered after the
        handle is sealed. Returns False if already finished/cancelled."""
        with self._step_lock:
            h = self._handles.get(rid)
            if h is None or h.done:
                return False
            with self._placed():
                self.core.cancel(rid)
            h._mark_cancelled()   # sealed under the step lock: no feed races
            self._streaming.discard(rid)
            if not self._streaming:
                self.core._stream_sync = False
            return True

    def _ensure_streaming(self, handle: RequestHandle) -> None:
        if handle.done:
            return        # tokens already delivered; nothing left to sync
        # the backfill reads scheduler/device state a concurrent stepping
        # thread mutates: take a whole-iteration slice of the step lock
        with self._step_lock:
            self._streaming.add(handle.rid)
            self.core._stream_sync = True
            self._backfill(handle)

    def _backfill(self, handle: RequestHandle) -> None:
        """Deliver any tokens this handle's slot emitted before (or
        between) streamed steps, straight from the device output buffer —
        keeps delivery contiguous when a consumer attaches mid-run."""
        for i, st in enumerate(self.core.sched.slots):
            if st.active and st.req.rid == handle.rid:
                if st.n_out > len(handle._toks):
                    out = np.asarray(self.core.out_buf[i, :st.n_out])  # inv-ok[R1]: one-off gap closure when a consumer attaches mid-stream, not on the step path
                    for j in range(len(handle._toks), st.n_out):
                        handle._feed(j, int(out[j]))
                return

    # -- step loop -------------------------------------------------------

    def warmup(self) -> float:
        # under the step lock: warmup touches the pools a concurrent
        # stepping thread would otherwise race
        with self._step_lock, self._placed():
            dt = self.core.warmup()
            # compile time is reported separately (stats['compile_s']); a
            # handle submitted before warmup should not charge it to TTFT
            now = time.perf_counter()
            for h in self._handles.values():
                if not h.done and h.ttft_s is None:
                    h._submit_s = max(h._submit_s, now)
            return dt

    def step(self) -> bool:
        """One engine iteration; returns True while work remains.

        Every step accrues its wall time into ``stats['decode_s']``, so
        throughput stays honest no matter what drives the loop — ``run()``,
        a ``RequestHandle`` iterator, or an external server loop.
        Thread-safe: handles on different threads (each blocking in
        ``result()``/``tokens()``) interleave whole iterations under one
        lock instead of racing the core state."""
        with self._step_lock, self._placed():
            t0 = time.perf_counter()
            self.core.step()
            self.core.stats["decode_s"] += time.perf_counter() - t0
            for rid, idx, tok in self.core.last_emitted:
                h = self._handles.get(rid)
                if h is not None:
                    if idx > len(h._toks):
                        self._backfill(h)  # close the gap before delivering
                    h._feed(idx, tok)
            finished = self.core.sched.finished
            for req, out in finished[self._finished_seen:]:
                h = self._handles.get(req.rid)
                if h is not None and not h.done:
                    h._finish(np.asarray(out, np.int32),
                              self.core.request_first_tok_t.get(req.rid))
                self._streaming.discard(req.rid)
            self._finished_seen = len(finished)
            if not self._streaming:
                # last streaming consumer done: restore the sync-free loop
                self.core._stream_sync = False
            return not self.core.sched.done()

    def run(self, max_steps: Optional[int] = None) -> Dict[int, np.ndarray]:
        """Drive the loop until every submitted request finished."""
        import jax
        steps = 0
        while self.step():
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        # attribute the tail of in-flight device work to decode time
        t0 = time.perf_counter()
        jax.block_until_ready(self.core.out_buf)  # inv-ok[R1]: end-of-run drain before wall-clock accounting
        self.core.stats["decode_s"] += time.perf_counter() - t0
        # snapshot under the submit lock: another thread may be inserting
        # handles while this one drains
        with self._submit_lock:
            handles = list(self._handles.items())
        return {rid: h._result for rid, h in handles if h.done}

    def reset(self) -> None:
        """Drop all requests/handles but keep the compiled executables.
        Serialised against concurrent step()/submit() callers — safe with
        a live front-end stepping thread: the thread is between
        iterations while we hold the step lock, every unfinished handle
        is marked stopped first (its consumers unblock with
        :class:`EngineStopped` instead of waiting on tokens that will
        never come), and the thread's next step() sees an empty engine
        and parks."""
        with self._step_lock, self._submit_lock:
            stranded = sum(1 for h in self._handles.values() if not h.done)
            if stranded:
                # post-mortem breadcrumb before the state is torn down
                self.core.obs.flight_dump("reset_with_live_requests")
            for h in self._handles.values():
                h._mark_stopped()
            with self._placed():
                self.core.reset()
            self._handles.clear()
            self._finished_seen = 0
            self._streaming.clear()

    def drain(self) -> None:
        """Block until every submitted request has finished (driving the
        loop here only when no stepping thread owns it)."""
        with self._submit_lock:
            handles = list(self._handles.values())
        for h in handles:
            if not h.cancelled:
                h.result()

    # -- introspection ---------------------------------------------------

    @property
    def stats(self) -> Dict:
        return self.core.stats

    @property
    def obs(self):
        """The core engine's :class:`repro.obs.Observability` bundle
        (metrics registry, span tracer, flight recorder, exporters)."""
        return self.core.obs

    def compiled_text(self) -> Dict[str, str]:
        """See :meth:`repro.serve.engine.ServeEngine.compiled_text`."""
        with self._step_lock:
            return self.core.compiled_text()

    @property
    def depth(self) -> int:
        """Requests in the system (queued + admitted) — the router's
        load signal."""
        return self.core.depth

    def prefix_probe(self, prompt) -> int:
        """Longest cached-prefix length this engine could reuse for
        ``prompt`` right now (0 without a prefix cache); read-only."""
        return self.core.prefix_probe(prompt)

    def accept_lens(self) -> Dict[int, List[int]]:
        """Per-request speculative accept-run lengths: rid -> list of
        accepted tokens per fused step (1 = all drafts rejected,
        draft_k + 1 = all survived). Finished or cancelled requests only;
        empty on a non-speculative engine."""
        return {rid: list(v)
                for rid, v in self.core.request_accept_lens.items()}

    def ttft(self) -> Dict[int, float]:
        """Per-request submit()->first-token wall seconds (finished or
        streaming requests only)."""
        with self._submit_lock:
            handles = list(self._handles.items())
        return {rid: h.ttft_s for rid, h in handles
                if h.ttft_s is not None}


def make_engine(cfg: ModelConfig, params, policy_params=None,
                **knobs) -> Engine:
    """Convenience: ``make_engine(cfg, params, n_slots=8, max_len=512)``
    builds the EngineConfig from keyword overrides."""
    return Engine(cfg, params, policy_params, config=EngineConfig(**knobs))
