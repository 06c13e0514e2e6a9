"""Continuous-batching serving engine.

One engine = one slot-paged KV cache + one scheduler + a small set of
compiled executables:

  * a slot-indexed **segment decision** (serve.policy) that re-picks a
    boundary slot's rank bucket from its live softmax-weighted layer-0 K
    spectra, refreshes its cached per-layer eigenbasis, and (in factor
    form) re-projects its paged K factors — one executable, one dispatch
    per boundary crossing,
  * ONE fused **decode step** over all slots (models.transformer.
    decode_step_paged): per-row kv_len, per-row rank via factor padding +
    rank masking, in-graph attention-mass accumulation, in-graph
    temperature/top-k sampling, and (by default) a factor-form score read
    ``kt = K . B_r`` that touches r_max/d of the dense K bytes —
    heterogeneous streams never force a recompile,
  * **chunked prefill** admission (``EngineConfig.prefill_chunk=C``):
    prompts are consumed C tokens at a time *inside* a mixed fused step
    that carries the live decode rows alongside — admission never stalls
    decoding, prompts of any length share one executable, and the
    chunk's causal attention mass accumulates into the slot's mass pool
    so the weighted-Gram basis still sees the full prompt mass.

Every setting comes from one :class:`repro.serve.config.EngineConfig`.
With ``prefix_cache=True`` finished prompts stay
cached in a radix tree (serve.prefix): admission matches the new prompt
against it, shares the hit's pages (refcounted, copy-on-write for a
partial tail page), rehydrates the slot's attention-mass row from the
prefix snapshot, and enters chunked prefill at the reuse point — token
output is identical to a cold admission that prefilled the whole prompt.

The step loop is host-side control only; lengths / ranks / tokens stay on
device between steps (token values are synced per step only when a live
request carries an ``eos_id`` or a streaming consumer is attached).
"""
from __future__ import annotations

import functools
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels.paged_attn import block_pages
from repro.models import transformer
from repro.models.api import get_model
from repro.obs import Observability, Stopwatch
from repro.serve.config import EngineConfig
from repro.serve.kv_cache import PagedKVCache
from repro.serve.policy import basis_drift, make_decide_fn
from repro.serve.prefix import MatchResult, PrefixCache
from repro.serve.spec import host_accept_stats
from repro.serve.scheduler import Request, Scheduler


class ServeEngine:
    """Continuous-batching decode over ``n_slots`` concurrent streams."""

    # adaptive draft length: an EWMA of the per-step accept fraction
    # drives an effective draft length eff_k in [0, draft_k]. The fused
    # executables are shape-static (draft_k forwards compile in), so
    # intermediate eff_k values only shorten the accept caps; the real
    # saving is eff_k == 0, where decode steps route through the mixed
    # step and skip the draft forwards entirely. A probe spec step every
    # _DRAFT_PROBE_EVERY steps samples the accept signal so a recovered
    # stream grows eff_k back.
    # cadence of recovery-probe spec steps while eff_k is collapsed to 0
    _DRAFT_PROBE_EVERY = 8
    # EWMA smoothing for the adaptive-draft accept-fraction signal
    _DRAFT_EWMA_ALPHA = 0.4

    def __init__(self, cfg: ModelConfig, params, policy_params=None, *,
                 config: Optional[EngineConfig] = None):
        self.cfg, self.params, self.policy = cfg, params, policy_params
        c = self.config = config or EngineConfig()
        self.seg = int(c.segment_len or cfg.rank.segment_len)
        self.trace = None
        if c.record_traces:
            from repro.serve.traces import TraceRecorder
            # a TraceRecorder instance may be shared across sequential
            # engines (one dataset over a whole workload suite); a fresh
            # path gets its own recorder
            self.trace = (c.record_traces
                          if isinstance(c.record_traces, TraceRecorder)
                          else TraceRecorder(c.record_traces, cfg))
        # observability bundle (repro.obs): the metrics registry shard is
        # always on (every stat below lives in it, via the StatsView);
        # span/phase tracing (obs_trace) and flight dumps (flight_dir)
        # are opt-in. Every hook the loop calls is host-only Python —
        # observability ON adds no device syncs and no executables.
        self.obs = Observability(trace=c.obs_trace, flight_dir=c.flight_dir,
                                 flight_capacity=c.flight_capacity)
        # speculative self-drafting (repro.serve.spec): draft_k cheap
        # low-rank tokens per fused step, verified in one chunked block.
        # The verify pass IS the chunked-query step; the step's chunk
        # width covers both the prefill chunk and the draft run.
        self.spec_chunk = (max(c.prefill_chunk, c.draft_k + 1)
                           if c.speculative else None)
        # factor_cache=None -> factor form whenever the rank path is on
        # AND the widest bucket is below the head dim (otherwise the
        # factor pool saves nothing). True forces it on (error without a
        # rank mode — there is no basis to factor against), False forces
        # the dense-K read; the benchmark uses both for the comparison.
        # prefix_cache grows the pool by ``prefix_pages`` (default: one
        # extra slot-set) so cached prefixes don't starve admissions.
        pps = -(-c.max_len // c.page_size)
        n_pages = None
        if c.prefix_cache:
            extra = (c.n_slots * pps if c.prefix_pages is None
                     else c.prefix_pages)
            n_pages = c.n_slots * pps + 1 + extra
        self.cache = PagedKVCache(cfg, c.n_slots, c.max_len, c.page_size,
                                  n_pages=n_pages, factored=c.factor_cache)
        # whether the plain fused steps (pure decode, mixed) attend with
        # the paged Pallas kernel: the choice decode_step_paged makes when
        # it is traced, repeated here for the serve.attn.* counters
        chosen = functools.partial(
            transformer.paged_attention_selected, cfg,
            max_len=self.cache.max_len, page_size=c.page_size,
            pool_dtype=self.cache.k_pool.dtype, factored=self.cache.factored)
        self._paged_attn = {False: chosen(chunk=1),
                            True: chosen(chunk=c.prefill_chunk)}
        self._attn_block = (block_pages(pps, c.page_size), c.page_size)
        # static draft width: the basis / kt pool are sliced to r_cap
        # columns for the draft forwards (a real byte cut); per-row draft
        # ranks (policy.draft_ranks) stay within [grid floor, r_cap]
        self._draft_cap = None
        self._grid_lo = None
        if c.speculative and self.cache.rank_on:
            # captured here, NOT read off cfg inside the traced body:
            # the jit closure must only see init-time immutables
            g_lo = int(cfg.rank.rank_grid[0])
            want = int(np.ceil(self.cache.r_keep * c.draft_rank_frac))
            self._draft_cap = min(max(g_lo, want, 1), self.cache.r_keep)
            self._grid_lo = g_lo
        self.prefix = PrefixCache(self.cache) if c.prefix_cache else None
        # submit() and admission (scheduler pop + device staging) may run
        # on different threads; one lock covers both critical sections
        self._lock = threading.Lock()
        # guards the decisions banked for the rank counters (appended by
        # the stepping thread, drained by whichever thread reads them)
        self._rank_lock = threading.Lock()
        self.sched = Scheduler(c.n_slots)
        self.fns = get_model(cfg)
        if self.fns.decode_step_paged is None:
            raise ValueError(
                f"family {cfg.family!r} has no paged decode step")
        self._decide = (make_decide_fn(cfg, policy_params)
                        if cfg.rank.mode != "off" else None)
        # donate the pools + out_buf so XLA updates them in place instead
        # of materialising a full copy per step (CPU ignores donation and
        # would warn, so only donate on real accelerators); warmup must
        # then re-capture the outputs — see warmup()
        donate = (() if jax.default_backend() == "cpu"
                  else (1, 2, 3, 4, 11))
        self._step = jax.jit(self._step_impl, donate_argnums=donate)
        self._step_mixed = jax.jit(self._step_mixed_impl,
                                   donate_argnums=donate)
        self._step_spec = (jax.jit(self._step_spec_impl,
                                   donate_argnums=donate)
                           if c.speculative else None)
        self._drift = (jax.jit(basis_drift)
                       if c.drift_threshold is not None else None)
        self._reset_state()

    def _reset_state(self):
        ns = self.config.n_slots
        k0 = self.config.draft_k if self.config.speculative else 0
        self.tokens = jnp.zeros((ns, 1), jnp.int32)
        # +1 scratch row: dead lanes park their garbage writes there
        self.out_buf = jnp.zeros((ns + 1, self.config.max_new_cap), jnp.int32)
        self.has_rank = np.zeros((ns,), bool)
        self.force_decide = np.zeros((ns,), bool)
        self.now = 0
        # device-resident control state: pushed only on admission/eviction
        # events (dirty flag), never per step — lens advances in-graph
        self._dirty = True
        self._pt_dev = None
        self._active_dev = None
        self._plen_dev = None
        self._lens_dev = None
        # per-slot sampling state (host mirrors; device copies pushed with
        # the control sync on admission)
        self._temp = np.zeros((ns,), np.float32)
        self._topk = np.zeros((ns,), np.int32)
        self._topp = np.ones((ns,), np.float32)
        self._seed = np.zeros((ns,), np.uint32)
        self._temp_dev = self._topk_dev = self._topp_dev = None
        self._seed_dev = None
        self._eos_dev = None
        self.prompt_buf = jnp.zeros((ns, self.cache.max_len), jnp.int32)
        # prefix-cache bookkeeping: the hit looked up at allocation time
        # (applied when the placement lands), the per-slot exact mass
        # snapshots captured during chunked prefill, and the inserted
        # nodes awaiting their lazy layer-0 spectra capture
        self._hits: Dict[int, MatchResult] = {}
        self._snaps: Dict[int, Dict[int, Optional[jnp.ndarray]]] = {}
        self._spectra_pending: Dict[int, object] = {}
        self.request_prefix_hit: Dict[int, bool] = {}
        # the historical stats dict as a view over the obs registry: same
        # keys, same dict semantics (reads, += writes, dict() copies),
        # but the registry is the single accumulation point and the
        # exporters see these values for free. Re-binding zeroes the
        # backing metrics — the old "fresh dict per reset" semantics.
        # Under the rank lock, so that a drain running on another thread
        # lands wholly before the re-zeroing or finds nothing banked.
        with self._rank_lock:
            self.stats = self.obs.stats_view(
                {"compile_s": 0.0, "decode_s": 0.0,
                 "steps": 0, "tokens_decoded": 0, "prefills": 0,
                 "decides": 0, "mixed_steps": 0,
                 "prefill_tokens": 0, "prefix_hits": 0,
                 "prefix_misses": 0, "prefix_reused_tokens": 0,
                 "prefix_cow": 0, "prefix_evictions": 0,
                 "spec_steps": 0, "spec_drafted": 0,
                 "spec_accepted": 0, "spec_tokens": 0,
                 "eff_draft_k": k0, "attn.paged_steps": 0,
                 "attn.pages_read": 0, "attn.pages_spanned": 0},
                refresh=self._drain_rank_counters)
            # per-decision (ranks, slot, vetoed) as UNFETCHED device values:
            # _drain_rank_counters fetches them in one batch when the rank
            # counters are read, so they cost the loop nothing (R1)
            self._rank_pending: List[Tuple[jnp.ndarray, int, jnp.ndarray]] = []
        self.obs.reset_run()
        # adaptive-draft controller state (host-only; never traced)
        self._eff_k = k0
        self._accept_ewma = 1.0
        self._probe_i = 0
        if self.trace is not None:
            # a reset ends every live stream: close their outcome windows
            for slot in range(ns):
                self.trace.on_evict(slot)
        # rid -> accepted run length of every speculative step the
        # request decoded in (harvested at eviction/cancel)
        self.request_accept_lens: Dict[int, List[int]] = {}
        self.rank_history: List[Tuple[int, jnp.ndarray, np.ndarray]] = []
        # harvested at eviction: decode-step wall time per token (needs
        # time_per_token=True) and first-token (prefill) latency per request
        self.token_latencies: List[float] = []
        self.first_token_s: List[float] = []
        # absolute perf_counter at each request's token-0 emission (the
        # api layer turns this into submit-relative TTFT)
        self.request_first_tok_t: Dict[int, float] = {}
        # (rid, out_index, token) triples of the last step, filled only
        # when the step synced token values (eos or _stream_sync)
        self.last_emitted: List[Tuple[int, int, int]] = []
        # streaming plane (repro.serve.api): when set, every step syncs
        # the emitted tokens to host and records them in ``last_emitted``
        # (the api layer turns it off again when the last streaming
        # consumer finishes, restoring the sync-free loop)
        self._stream_sync = False

    def reset(self):
        """Clear all serving state — including every cached prefix — but
        keep the compiled executables. Takes the engine lock: a submit
        racing a reset either lands before (and is discarded with the old
        scheduler's queue) or after (and is served) — never silently
        orphaned in a swapped-out scheduler."""
        with self._lock:
            cfg, c = self.cfg, self.cache
            self.cache = PagedKVCache(cfg, self.config.n_slots, c.max_len,
                                      c.page_size, n_pages=c.n_pages,
                                      factored=c.factored)
            if self.prefix is not None:
                self.prefix = PrefixCache(self.cache)
            self.sched = Scheduler(self.config.n_slots)
            self._reset_state()

    # -- request plane ---------------------------------------------------

    def submit(self, req: Request) -> None:
        """Enqueue a request. Thread-safe: the queue append is serialised
        against the step loop's admission (scheduler pop + device staging)
        by the engine lock, so a server thread may submit while another
        drives step()/run() — the stepping stone to a fully async API."""
        if req.max_new > self.config.max_new_cap:
            raise ValueError(f"max_new {req.max_new} > engine cap "
                             f"{self.config.max_new_cap}")
        if (self.cache.pages_needed(len(req.tokens) + req.max_new)
                > self.cache.pages_per_slot):
            raise ValueError(
                f"request needs {len(req.tokens) + req.max_new} cache "
                f"positions but a slot holds only {self.cache.max_len}")
        if ((req.temperature > 0 or req.top_k > 0 or req.top_p < 1.0)
                and not self.config.sampling):
            raise ValueError("request asks for sampling but the engine was "
                             "built with sampling=False (greedy executable)")
        if req.top_p < 1.0 and not self.config.nucleus:
            raise ValueError("request asks for top_p but the engine was "
                             "built with nucleus=False (the top-p cut is "
                             "a compiled-in full-vocab sort per step; "
                             "build the engine with nucleus=True)")
        if req.top_k > self.config.top_k_cap:
            raise ValueError(f"top_k {req.top_k} > engine top_k_cap "
                             f"{self.config.top_k_cap}")
        with self._lock:
            self.sched.submit(req)

    def cancel(self, rid: int) -> bool:
        """Abort a request mid-flight: a queued request is dropped, an
        admitted one (decoding OR mid-prefill) is evicted and its pages
        released (refcounted: pages shared with the prefix tree or
        another slot stay resident). Returns False when ``rid`` is not
        live (already finished, already cancelled, or never submitted).

        Callers driving a concurrent step loop must serialise this
        against step() (repro.serve.api.Engine.cancel holds the step
        lock) — the engine lock here only guards the queue and the page
        accounting against a racing submit/admission."""
        with self._lock:
            if self.sched.cancel_pending(rid):
                return True
            for i, st in enumerate(self.sched.slots):
                if st.active and st.req.rid == rid:
                    outputs = np.asarray(self.out_buf[i, :st.n_out]).tolist()
                    if st.accept_lens:
                        self.request_accept_lens[rid] = list(st.accept_lens)
                    if self.trace is not None:
                        self.trace.on_evict(i)
                    self.obs.on_finish(rid, i, st.n_out, "cancel")
                    self.sched.evict(i, self.cache.release, outputs)
                    # a mid-prefill cancel leaves no prefix insertion and
                    # no pending spectra capture for this slot
                    self._hits.pop(i, None)
                    self._snaps.pop(i, None)
                    self._spectra_pending.pop(i, None)
                    self._dirty = True
                    return True
        return False

    @property
    def depth(self) -> int:
        """Queue depth: pending + admitted requests (the router's
        load-balancing signal)."""
        with self._lock:
            return self.sched.depth()

    def prefix_probe(self, tokens) -> int:
        """Longest cached-prefix length this engine could reuse for
        ``tokens`` right now (0 without a prefix cache). Read-only — the
        router scores every replica with this before dispatching."""
        if self.prefix is None:
            return 0
        with self._lock:
            return self.prefix.probe(tokens)

    def _adopt_pools(self, pools) -> None:
        """Re-capture the fused step's (donated) pool outputs.  The
        optional factor/mass pools are adopted only when the step
        returned them — never by re-reading the donated input as a
        fallback, which a donating backend may already have
        invalidated."""
        self.cache.k_pool, self.cache.v_pool = pools["k"], pools["v"]
        if "kt" in pools:
            self.cache.kt_pool = pools["kt"]
        if "mass" in pools:
            self.cache.mass_pool = pools["mass"]

    def warmup(self) -> float:
        """Compile (and run once, results discarded) every executable the
        queued requests will need; the elapsed time lands in
        stats['compile_s'] so throughput numbers stay compile-free."""
        sw = Stopwatch()
        ns = self.config.n_slots
        self._sync_control()
        if self._decide is not None:
            # donated args (basis/spectra/kt) must be re-captured; the
            # warm decision runs on the empty slot 0 whose state the
            # admission-time re-decision overwrites before any read
            # (the warm veto flag is meaningless and not banked)
            (self.cache.ranks, self.cache.basis, self.cache.spectra,
             self.cache.kt_pool, _veto) = self._decide(
                self.cache.k_pool, self.cache.mass_pool, self.cache.kt_pool,
                self._pt_dev, self._lens_dev, self.cache.ranks,
                self.cache.basis, self.cache.spectra,
                np.int32(0), np.bool_(False), np.int32(0))
            jax.block_until_ready(self.cache.basis)
        # all-lanes-inactive step: writes land on the scratch page / row,
        # so re-capturing the donated pools and out_buf is value-neutral
        if self.config.speculative:
            # the pure-prefill phase routes through the mixed step (see
            # step()); the plain decode step is never dispatched
            pools, tok, ob, _ = self._step_mixed(
                self.params, self.cache.k_pool, self.cache.v_pool,
                self.cache.kt_pool, self.cache.mass_pool,
                self._pt_dev, self.tokens, self._lens_dev,
                self.cache.ranks, self.cache.basis,
                jnp.zeros((ns,), bool), self.out_buf,
                self._plen_dev, self._temp_dev, self._topk_dev,
                self._topp_dev, self._seed_dev, self.prompt_buf)
            self._adopt_pools(pools)
            self.out_buf = ob
            jax.block_until_ready(tok)
            pools, tok, ob, _, _, _, _ = self._step_spec(
                self.params, self.cache.k_pool, self.cache.v_pool,
                self.cache.kt_pool, self.cache.mass_pool,
                self._pt_dev, self.tokens, self._lens_dev,
                self.cache.ranks, self.cache.basis,
                jnp.zeros((ns,), bool), self.out_buf,
                self._plen_dev, self._temp_dev, self._topk_dev,
                self._topp_dev, self._seed_dev, self.prompt_buf,
                self.cache.spectra, jnp.ones((ns,), jnp.int32),
                jnp.full((ns,), -1, jnp.int32))
            self._adopt_pools(pools)
            self.out_buf = ob
            jax.block_until_ready(tok)
        else:
            for fn, extra in ((self._step, ()),
                              (self._step_mixed, (self.prompt_buf,))):
                pools, tok, ob, _ = fn(
                    self.params, self.cache.k_pool, self.cache.v_pool,
                    self.cache.kt_pool, self.cache.mass_pool,
                    self._pt_dev, self.tokens, self._lens_dev,
                    self.cache.ranks, self.cache.basis,
                    jnp.zeros((ns,), bool), self.out_buf,
                    self._plen_dev, self._temp_dev, self._topk_dev,
                    self._topp_dev, self._seed_dev, *extra)
                self._adopt_pools(pools)
                self.out_buf = ob
                jax.block_until_ready(tok)
        dt = sw.stop()
        self.stats["compile_s"] += dt
        return dt

    def compiled_text(self) -> Dict[str, str]:
        """``{HLO module name: optimized HLO text}`` of the fused steps and
        the segment decision this engine dispatches, for attributing a
        profiler trace's device ops to the ``jax.named_scope`` names in
        their ``op_name`` metadata. Call after :meth:`warmup`: lowering
        with the arguments' current shapes finds the executables jit
        already compiled, so nothing is compiled again."""
        ns = self.config.n_slots
        fused = (self.params, self.cache.k_pool, self.cache.v_pool,
                 self.cache.kt_pool, self.cache.mass_pool, self._pt_dev,
                 self.tokens, self._lens_dev, self.cache.ranks,
                 self.cache.basis, self._active_dev, self.out_buf,
                 self._plen_dev, self._temp_dev, self._topk_dev,
                 self._topp_dev, self._seed_dev)
        calls = [(self._step, fused),
                 (self._step_mixed, fused + (self.prompt_buf,)),
                 (self._step_spec, fused + (
                     self.prompt_buf, self.cache.spectra,
                     jnp.ones((ns,), jnp.int32), self._eos_dev)),
                 (self._decide, (
                     self.cache.k_pool, self.cache.mass_pool,
                     self.cache.kt_pool, self._pt_dev, self._lens_dev,
                     self.cache.ranks, self.cache.basis, self.cache.spectra,
                     np.int32(0), np.bool_(False), np.int32(0)))]
        if self.config.speculative:
            calls.pop(0)        # the plain step is never warmed (see step())
        out = {}
        for fn, args in calls:
            if fn is not None:
                text = fn.lower(*args).compile().as_text()
                out[text.split(",", 1)[0].split()[-1]] = text
        return out

    # -- data plane ------------------------------------------------------

    def _select_token(self, logits, out_pos, temps, topks, topps, seeds):
        """Next token per row from (ns, V) logits. ``out_pos`` is each
        row's output index (0 = first generated token): the sampling PRNG
        folds (per-request seed, out_pos), so a stream's draw sequence is
        a pure function of the request — identical under any batching or
        chunking. Greedy rows (temperature 0) take the plain argmax,
        bitwise identical to the sampling-free executable.

        Filter order matches the common stack: temperature scale -> top-k
        -> top-p (nucleus: the smallest probability-sorted set whose mass
        reaches ``top_p``; at least one token survives; probability ties
        at the cut all stay in). ``top_p == 1`` rows bypass the nucleus
        mask bitwise, so greedy / top-k / top-p streams mix in ONE
        executable — but the cut itself (full-vocab softmax + sort per
        step) is only compiled in when the engine was built with
        ``nucleus=True``."""
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if not self.config.sampling:
            return greedy
        ns, V = logits.shape
        kcap = min(self.config.top_k_cap, V)
        kth = jax.lax.top_k(logits, kcap)[0]                  # (ns, kcap)
        sel = jnp.clip(topks - 1, 0, kcap - 1)
        thr = jnp.take_along_axis(kth, sel[:, None], 1)
        masked = jnp.where((topks[:, None] > 0) & (logits < thr),
                           -jnp.inf, logits)
        t = jnp.maximum(temps, 1e-6)[:, None]
        scaled = masked / t
        if self.config.nucleus:
            # nucleus cut: keep tokens whose probability is >= the
            # smallest probability still inside the top_p mass
            # (sorted-cumsum rule)
            pr = jax.nn.softmax(scaled, axis=-1)
            srt = jnp.sort(pr, axis=-1)[:, ::-1]
            cum = jnp.cumsum(srt, axis=-1)
            keep = (cum - srt) < topps[:, None]   # mass before token < p
            p_min = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1,
                            keepdims=True)
            scaled = jnp.where((topps[:, None] < 1.0) & (pr < p_min),
                               -jnp.inf, scaled)
        keys = jax.vmap(lambda s, p: jax.random.fold_in(
            jax.random.PRNGKey(s), p))(seeds, out_pos.astype(jnp.uint32))
        g = jax.vmap(lambda k: jax.random.gumbel(k, (V,)))(keys)
        sampled = jnp.argmax(scaled + g, axis=-1).astype(jnp.int32)
        return jnp.where(temps > 0, sampled, greedy)

    def _step_impl(self, params, pool_k, pool_v, kt_pool, mass_pool,
                   page_table, tokens, lens, ranks, basis, active, out_buf,
                   prompt_lens, temps, topks, topps, seeds):
        ns = tokens.shape[0]
        off = self.cfg.rank.mode == "off"
        logits, pools = self.fns.decode_step_paged(
            params, pool_k, pool_v, page_table, tokens,
            slot_lens=lens, slot_ranks=None if off else ranks,
            basis=None if off else basis, active=active,
            use_kernel=self.config.use_kernel,
            kt_pool=None if off else kt_pool,
            mass_pool=None if off else mass_pool)
        with jax.named_scope("sample"):
            out_idx = jnp.where(active, jnp.minimum(
                lens - prompt_lens + 1, self.config.max_new_cap - 1), 0)
            tok = self._select_token(logits[:, 0], out_idx,
                                     temps, topks, topps, seeds)[:, None]
            tok = jnp.where(active[:, None], tok, tokens)
            row = jnp.where(active, jnp.arange(ns), ns)   # dead -> scratch row
            out_buf = out_buf.at[row, out_idx].set(tok[:, 0])
        lens = lens + active.astype(lens.dtype)
        return pools, tok, out_buf, lens

    def _step_mixed_impl(self, params, pool_k, pool_v, kt_pool, mass_pool,
                         page_table, tokens, lens, ranks, basis, active,
                         out_buf, prompt_lens, temps, topks, topps, seeds,
                         prompt_buf):
        """One mixed fused step: live decode rows advance one token while
        mid-prefill rows consume the next ``chunk`` tokens of their prompt
        from the device-resident ``prompt_buf`` — chunked prefill
        interleaved into the decode step, no host work in between."""
        ns, C = tokens.shape[0], self.config.prefill_chunk
        off = self.cfg.rank.mode == "off"
        is_pf = active & (lens < prompt_lens)
        q_lens = jnp.where(is_pf, jnp.minimum(C, prompt_lens - lens),
                           1).astype(jnp.int32)
        idx = jnp.clip(lens[:, None] + jnp.arange(C)[None, :], 0,
                       prompt_buf.shape[1] - 1)
        chunk_toks = jnp.take_along_axis(prompt_buf, idx, axis=1)
        toks_in = jnp.where(is_pf[:, None], chunk_toks,
                            jnp.broadcast_to(tokens, (ns, C)))
        logits, pools = self.fns.decode_step_paged(
            params, pool_k, pool_v, page_table, toks_in,
            slot_lens=lens, q_lens=q_lens, prefill_rows=is_pf,
            slot_ranks=None if off else ranks,
            basis=None if off else basis, active=active,
            use_kernel=self.config.use_kernel,
            kt_pool=None if off else kt_pool,
            mass_pool=None if off else mass_pool)
        lens_after = lens + jnp.where(active, q_lens, 0)
        finishing = is_pf & (lens_after >= prompt_lens)
        emit = active & (finishing | ~is_pf)
        with jax.named_scope("sample"):
            out_idx = jnp.where(emit, jnp.clip(lens_after - prompt_lens, 0,
                                               self.config.max_new_cap - 1), 0)
            tok = self._select_token(logits[:, 0], out_idx,
                                     temps, topks, topps, seeds)[:, None]
            tok = jnp.where(emit[:, None], tok, tokens)
            row = jnp.where(emit, jnp.arange(ns), ns)     # no-emit -> scratch
            out_buf = out_buf.at[row, out_idx].set(tok[:, 0])
        return pools, tok, out_buf, lens_after

    def _step_spec_impl(self, params, pool_k, pool_v, kt_pool, mass_pool,
                        page_table, tokens, lens, ranks, basis, active,
                        out_buf, prompt_lens, temps, topks, topps, seeds,
                        prompt_buf, spectra, caps, eos_ids):
        """One fused speculative step (repro.serve.spec): ``draft_k``
        single-token forwards at an aggressive per-row draft rank over a
        statically narrowed basis / factor slice, then ONE chunked verify
        block per row at the slot's current rank, longest-prefix accept
        with EOS / budget / segment-boundary clamps, and an in-graph
        logical rollback — ``lens`` advances past accepted tokens only;
        rejected positions are masked garbage the next step overwrites.
        Mid-prefill rows ride along exactly as in the mixed step. Deferred
        per-query mass contributions are applied for accepted queries
        only, in query order (bitwise the sequential accumulation).

        Returns (pools, tok, out_buf, lens_after, accepts, n_emit,
        emitted): ``accepts`` (ns,) the accepted run length per
        speculative row (0 elsewhere), ``n_emit`` (ns,) tokens emitted
        per row this step, ``emitted`` (ns, draft_k + 1) their values
        (col 0 = token 0 for a row finishing its prompt)."""
        from repro.serve import spec as spec_mod
        from repro.serve.policy import draft_ranks
        ns = tokens.shape[0]
        off = self.cfg.rank.mode == "off"
        k_d = self.config.draft_k
        Cd = k_d + 1
        C = self.spec_chunk
        cap = self.config.max_new_cap
        is_pf = active & (lens < prompt_lens)
        spec_rows = active & ~is_pf
        base_out = jnp.clip(lens - prompt_lens + 1, 0, cap - 1)

        # -- draft: k_d cheap forwards. Draft K/V writes land in the live
        # pages — every one of them sits in the verify block's write range
        # [lens, lens + Cd) and is overwritten there with authoritative
        # values. Draft factor appends go into the sliced transient copy
        # (discarded); the mass pool is never touched by drafts, so the
        # Eq. 9 veto state only ever sees accepted tokens.
        if off:
            d_ranks = d_basis = d_kt = None
        else:
            d_ranks = draft_ranks(ranks, spectra,
                                  frac=self.config.draft_rank_frac,
                                  grid_lo=self._grid_lo,
                                  r_cap=self._draft_cap)
            d_basis = basis[..., :self._draft_cap]
            d_kt = (None if kt_pool is None
                    else kt_pool[..., :self._draft_cap])
        pk, pv = pool_k, pool_v
        d_tok = tokens
        drafts = []
        for i in range(k_d):
            dlg, dpools = self.fns.decode_step_paged(
                params, pk, pv, page_table, d_tok,
                slot_lens=lens + i, slot_ranks=d_ranks, basis=d_basis,
                active=spec_rows, use_kernel=self.config.use_kernel,
                kt_pool=d_kt, mass_pool=None)
            pk, pv = dpools["k"], dpools["v"]
            d_kt = dpools.get("kt", d_kt)
            with jax.named_scope("sample"):
                opos = jnp.minimum(base_out + i, cap - 1)
                d_tok = self._select_token(dlg[:, 0], opos, temps, topks,
                                           topps, seeds)[:, None]
            drafts.append(d_tok[:, 0])
        drafts = jnp.stack(drafts, axis=1)                   # (ns, k_d)

        # -- verify: one causal chunk [t_0, d_1..d_k] per speculative row
        # (the next prompt chunk for mid-prefill rows) at the slot's
        # CURRENT rank — the same read plain decode would have done, so
        # every accepted token is exact by construction
        q_lens = jnp.where(is_pf, jnp.minimum(C, prompt_lens - lens),
                           Cd).astype(jnp.int32)
        idx = jnp.clip(lens[:, None] + jnp.arange(C)[None, :], 0,
                       prompt_buf.shape[1] - 1)
        chunk_toks = jnp.take_along_axis(prompt_buf, idx, axis=1)
        spec_toks = jnp.concatenate([tokens, drafts], axis=1)    # (ns, Cd)
        if C > Cd:
            spec_toks = jnp.pad(spec_toks, ((0, 0), (0, C - Cd)))
        toks_in = jnp.where(is_pf[:, None], chunk_toks, spec_toks)
        defer = (not off) and (mass_pool is not None)
        logits, pools = self.fns.decode_step_paged(
            params, pk, pv, page_table, toks_in,
            slot_lens=lens, q_lens=q_lens, prefill_rows=is_pf,
            slot_ranks=None if off else ranks,
            basis=None if off else basis, active=active,
            use_kernel=self.config.use_kernel,
            kt_pool=None if off else kt_pool,
            mass_pool=None, return_all_logits=True, mass_defer=defer)
        # target tokens at every position, same (seed, out position) fold
        # as plain decode — the sampler is deterministic per position, so
        # "accept while draft == target" reproduces plain decode exactly.
        # A finishing prefill row emits output index 0 (only its final
        # query's sample is ever read), matching the mixed step's fold.
        opos = jnp.where(is_pf[:, None], 0,
                         jnp.minimum(base_out[:, None]
                                     + jnp.arange(C)[None, :],
                                     cap - 1))                   # (ns, C)
        with jax.named_scope("sample"):
            g = jax.vmap(self._select_token,
                         in_axes=(1, 1, None, None, None, None),
                         out_axes=1)(logits, opos, temps, topks, topps, seeds)

        tgt = g[:, :Cd]
        a = spec_mod.accept_counts(drafts, tgt)
        a = spec_mod.clamp_to_eos(a, tgt, eos_ids)
        a = jnp.minimum(a, caps)
        a = jnp.where(spec_rows, a, 0)

        lens_after = lens + jnp.where(is_pf, q_lens, 0) + a
        finishing = is_pf & (lens_after >= prompt_lens)
        n_emit = a + finishing.astype(a.dtype)
        fin_tok = jnp.take_along_axis(g, (q_lens - 1)[:, None], axis=1)
        src = jnp.where(finishing[:, None],
                        jnp.broadcast_to(fin_tok, tgt.shape), tgt)
        emit_ok = jnp.arange(Cd)[None, :] < n_emit[:, None]
        rows = jnp.where(emit_ok, jnp.arange(ns)[:, None], ns)
        col0 = jnp.where(finishing, 0, base_out)
        cols = jnp.clip(col0[:, None] + jnp.arange(Cd)[None, :], 0, cap - 1)
        with jax.named_scope("sample"):
            out_buf = out_buf.at[rows, cols].set(src)
        last = jnp.take_along_axis(
            src, jnp.clip(n_emit - 1, 0, Cd - 1)[:, None], axis=1)
        tok = jnp.where(n_emit[:, None] > 0, last, tokens)

        if defer:
            contrib = pools.pop("mass_q")
            n_q = jnp.where(spec_rows, a, jnp.where(is_pf, q_lens, 0))
            pools["mass"] = spec_mod.apply_deferred_mass(
                mass_pool, contrib, lens, n_q)
        return pools, tok, out_buf, lens_after, a, n_emit, src

    def _sync_control(self) -> None:
        """Push host control state to device after admission/eviction; the
        steady-state decode loop reuses these arrays without any transfer."""
        if not self._dirty:
            return
        self._pt_dev = jnp.asarray(self.cache.page_table)
        self._active_dev = jnp.asarray(
            np.array([s.active for s in self.sched.slots]))
        self._plen_dev = jnp.asarray(
            np.array([s.prompt_len if s.active else 0
                      for s in self.sched.slots], np.int32))
        self._lens_dev = jnp.asarray(self.cache.lens, jnp.int32)
        self._temp_dev = jnp.asarray(self._temp)
        self._topk_dev = jnp.asarray(self._topk)
        self._topp_dev = jnp.asarray(self._topp)
        self._seed_dev = jnp.asarray(self._seed)
        self._eos_dev = jnp.asarray(
            np.array([s.req.eos_id
                      if (s.active and s.req.eos_id is not None) else -1
                      for s in self.sched.slots], np.int32))
        self._dirty = False

    def _can_allocate(self, slot: int, total_len: int) -> bool:
        """Page-reservation hook for the scheduler, called for the head of
        the pending queue. With a prefix cache, the head request's prompt
        is matched first: a hit's shared pages become the slot's leading
        page-table entries (ref + 1, no prefill over them), under pool
        pressure the tree evicts LRU leaves (the matched path is pinned),
        and the hit is stashed for the placement that follows."""
        if self.prefix is None:
            return self.cache.allocate(slot, total_len)
        req = self.sched.pending[0]
        hit = self.prefix.match(req.tokens)
        # a partially-filled shared tail page is copied, not shared: the
        # slot appends into it from the reuse point (copy-on-write), so
        # allocation must draw its replacement from the free list
        shared = hit.pages[:-1] if hit.cow_src is not None else hit.pages
        shortfall = (self.cache.pages_needed(total_len) - len(shared)
                     - self.cache.free_pages)
        if shortfall > 0:
            # stats count PAGES evicted from the tree (evict_lru's return)
            self.stats["prefix_evictions"] += self.prefix.evict_lru(
                shortfall, protect=hit.nodes)
        if not self.cache.allocate(slot, total_len, prefix_pages=shared):
            return False
        # LRU recency advances only for a committed HIT — neither a head
        # request re-matching every step while blocked on pages, nor a
        # miss that merely grazed the path, may inflate it
        if hit.reuse_len > 0:
            self.prefix.touch_path(hit.nodes)
        self._hits[slot] = hit
        return True

    def _apply_prefix_hit(self, slot: int, req: Request) -> int:
        """Rehydrate a prefix hit at admission: COW the shared tail page if
        partial, mark the matched tokens prefilled, and re-seed the slot's
        per-stream low-rank state (mass row, spectra) from the snapshot so
        the first segment decision is identical to a cold admission's.
        Returns the number of reused prompt tokens."""
        hit = self._hits.pop(slot, None)
        st = self.sched.slots[slot]
        m = 0 if hit is None else hit.reuse_len
        if hit is not None:
            self.request_prefix_hit[req.rid] = m > 0
            self.stats["prefix_hits" if m > 0 else "prefix_misses"] += 1
            self.stats["prefix_reused_tokens"] += m
        if m > 0:
            if hit.cow_src is not None:
                dst = int(self.cache.page_table[slot,
                                                m // self.cache.page_size])
                self.cache.copy_page(dst, hit.cow_src)
                self.stats["prefix_cow"] += 1
            st.prefilled = m
            self.cache.lens[slot] = m
            if hit.spectra is not None and self.cache.spectra is not None:
                # informational warm start; the first decision overwrites
                # it (veto disabled via has_rank), so parity is untouched
                self.cache.spectra = self.cache.spectra.at[slot].set(
                    hit.spectra)
        if m > 0 and hit.mass is not None and self.cache.mass_pool is not None:
            # re-seed the matched prefix from the snapshot (exact: the
            # cumulative mass of queries [0, m) over positions [0, m)).
            # Cells beyond m need no zeroing — the fused step resets each
            # cell in-graph the step its position is appended.
            self.cache.mass_pool = self.cache.mass_pool.at[:, slot, :m].set(
                hit.mass)
        return m

    def _admit(self) -> List[int]:
        with self._lock:
            return self._admit_locked()

    def _admit_locked(self) -> List[int]:
        """Stage each placed prompt on device and let the mixed fused
        steps consume it — no model work here, the loop never stalls on
        a prefill. A prefix hit skips its reused tokens: chunked prefill
        starts at the reuse point."""
        placed = self.sched.admit(self.now, self._can_allocate)
        for slot, req in placed:
            st = self.sched.slots[slot]
            st.admit_s = time.perf_counter()
            # a recycled slot must not inherit its previous occupant's
            # rank state: first decision is veto-free, fresh clock
            self.has_rank[slot] = False
            self.force_decide[slot] = False
            self._spectra_pending.pop(slot, None)
            self._temp[slot] = req.temperature
            self._topk[slot] = req.top_k
            self._topp[slot] = req.top_p
            self._seed[slot] = np.uint32(req.seed)
            buf = np.zeros((self.cache.max_len,), np.int32)
            buf[:len(req.tokens)] = req.tokens
            self.prompt_buf = self.prompt_buf.at[slot].set(jnp.asarray(buf))
            m = self._apply_prefix_hit(slot, req)
            self._snaps[slot] = {}
            self.stats["prefill_tokens"] += st.prompt_len - m
            self.obs.on_admit(req.rid, slot, st.prompt_len, reused=m)
        if placed:
            self._dirty = True
        return [slot for slot, _ in placed]

    def _stamp_first_token(self, i: int, st, now_t: float,
                           ttft_s: float) -> None:
        """Shared first-token bookkeeping for the two prefill-completion
        paths (mixed step, spec step): output count, TTFT latency,
        stats, and the obs hook."""
        st.n_out = 1                              # token 0 emitted
        st.latencies.append(ttft_s)               # first-token latency
        self.stats["prefills"] += 1
        self.request_first_tok_t[st.req.rid] = now_t
        self.obs.on_first_token(st.req.rid, i, ttft_s)

    def _maybe_decide(self) -> None:
        if self._decide is None:
            return
        # mid-prefill slots are excluded: their prompt mass / K run is
        # still incomplete, and decode_i == 0 will still be a boundary at
        # their first decode step
        active = np.array([s.active and not s.mid_prefill
                           for s in self.sched.slots])
        at_seg = np.array([s.decode_i % self.seg == 0
                           for s in self.sched.slots])
        boundary = active & (at_seg | self.force_decide)
        if not boundary.any():
            return
        self._sync_control()
        # per-slot decision, slot index traced: streams hit segment
        # boundaries on their own staggered clocks, so an all-slots batched
        # decide would redo every slot's spectral solve at the union of
        # boundaries — n_slots times the work a per-stream server pays.
        # One dispatch per boundary crossing, one executable for all slots.
        for i in np.nonzero(boundary)[0]:
            st = self.sched.slots[i]
            first = not self.has_rank[i]
            forced = bool(self.force_decide[i])
            (self.cache.ranks, self.cache.basis, self.cache.spectra,
             self.cache.kt_pool, vetoed) = self._decide(
                self.cache.k_pool, self.cache.mass_pool, self.cache.kt_pool,
                self._pt_dev, self._lens_dev, self.cache.ranks,
                self.cache.basis, self.cache.spectra, np.int32(i),
                np.bool_(self.has_rank[i]), np.int32(st.t))
            # the chosen rank and the Eq. 9 veto flag stay on the
            # device: bank them UNFETCHED for the rank counters, which
            # read the whole batch in one device_get (no sync here)
            with self._rank_lock:
                self._rank_pending.append((self.cache.ranks, int(i), vetoed))
                self.stats["decides"] += 1
            st.t += 1
            self.obs.on_decide(int(i), st.t - 1, forced=forced)
            if self.trace is not None:
                s2_h, rank_h = jax.device_get(  # inv-ok[R1]: trace recording fetches the decision's spectra/rank once per segment boundary (the decide cadence), never per decode step
                    (self.cache.spectra[i], self.cache.ranks[i]))
                self.trace.on_decision(
                    int(i), st.req.rid, st.t - 1,
                    int(self.cache.lens[i]), int(rank_h),
                    np.asarray(s2_h), has_prev=not first)
            if first:
                # lazy prefix-snapshot completion: the slot's first
                # decision is the prompt decision — persist its layer-0
                # spectra on the cached prefix node (informational warm
                # start for future hits; parity-neutral)
                node = self._spectra_pending.pop(i, None)
                if node is not None:
                    node.snap_spectra = self.cache.spectra[i]
        self.has_rank |= boundary
        self.force_decide &= ~boundary

    def _drain_rank_counters(self) -> None:
        """Add the banked decisions into ``stats['rank_chosen_sum']``
        (the rank each chose) and ``stats['rank_vetoes']`` (Eq. 9 veto
        fires). Called by the stats view when either is read, never by
        the step loop: one batched fetch of values ``decide`` already
        returned. Holding the lock that the decision path takes to bank
        a decision and count it in ``decides`` keeps the three in step."""
        with self._rank_lock:
            pend, self._rank_pending = self._rank_pending, []
            if not pend:
                return
            got = jax.device_get([(r, v) for r, _, v in pend])
            self.obs.on_ranks_fetched(
                sum(int(r[s]) for (r, _), (_, s, _) in zip(got, pend)),
                sum(bool(v) for _, v in got))

    def _check_drift(self, live: List[int]) -> None:
        """Early re-decision trigger: measure the newest K token's residual
        energy outside each live slot's stored basis and set
        ``force_decide`` where it exceeds ``drift_threshold``.

        Clock semantics under speculation (tested in
        tests/test_serve_spec.py): the check fires once per fused step —
        i.e. once per *accepted run*, not once per token — and always
        against the **post-accept position**: both call sites run after
        ``cache.lens`` has advanced past the accepted tokens, so the K
        token inspected is the last one the verify pass actually wrote.
        A drifting stream therefore re-decides at most one accepted run
        (<= draft_k tokens) later than plain decode would, and the forced
        re-decision lands at the next step's ``_maybe_decide`` — before
        that step's fused dispatch. Token streams may legally diverge
        from plain decode under drift + speculation (the re-decision
        clock is coarser); with drift off (the default) speculation stays
        bitwise exact."""
        ns, ps = self.config.n_slots, self.cache.page_size
        pos = np.maximum(self.cache.lens - 1, 0)
        phys = self.cache.page_table[np.arange(ns), pos // ps]
        k_tok = self.cache.k_pool[0, jnp.asarray(phys),
                                  jnp.asarray(pos % ps)].reshape(
            ns, self.cfg.num_kv_heads, -1)
        drift = np.asarray(  # inv-ok[R1]: drift check runs on the decide cadence (every decide_every steps), one small-vector fetch, never per decode step
            self._drift(k_tok, self.cache.basis[0], self.cache.ranks))
        for i in live:
            if self.has_rank[i] and drift[i] > self.config.drift_threshold:
                self.force_decide[i] = True
                self.obs.on_drift(int(i), float(drift[i]))

    def _maybe_snapshot(self, i: int, st, done_pf: bool) -> None:
        """Capture a cumulative-mass snapshot for the prefix cache. The
        accumulator holds queries [0, prefilled) and nothing more because
        chunked prefill paused exactly here. ``snapshot_every`` throttles
        density: only every k-th page boundary is kept (plus the prompt
        end, which anchors the full-prompt node); prefix probe/match fall
        back to the nearest earlier snapshot, trading a slightly shorter
        hit for O(P^2 / (k * ps)) snapshot bytes per prompt."""
        if self.prefix is None:
            return
        ps = self.cache.page_size
        at_page = st.prefilled % ps == 0
        every = self.config.snapshot_every
        kept = at_page and (st.prefilled // ps) % every == 0
        if done_pf or kept:
            self._snaps[i][st.prefilled] = (
                None if self.cache.mass_pool is None else
                self.cache.mass_pool[:, i, :st.prefilled])

    def _insert_prefix(self, i: int, st) -> None:
        """Publish a finished prompt's pages + snapshots to the radix
        tree; the node waits for its spectra at the next decision."""
        if self.prefix is None:
            return
        n_pg = self.cache.pages_needed(st.prompt_len)
        node = self.prefix.insert(
            st.req.tokens,
            [int(p) for p in self.cache.page_table[i, :n_pg]],
            self._snaps.pop(i, {}))
        if node is not None and self._decide is not None:
            self._spectra_pending[i] = node
        # gauge cadence: once per finished prompt (pages counted as
        # distinct physical ids — COW shares collapse)
        self.obs.set_prefix_size(
            self.prefix.n_nodes, len(set(self.prefix.all_pages())))

    def _step_live_spec(self, live: List[int], ph) -> None:
        """Host side of one speculative engine iteration (the fused body
        is _step_spec_impl). ``ph`` is the step's phase recorder. Differs from the plain path in three ways:
        decode rows advance by their accepted run length ``a`` (1..
        draft_k + 1) instead of 1; the per-step accept/emission fetch IS
        the token stream (handles get every accepted token, not just the
        newest); and the host caps each row's accepts so max_new, and —
        in adaptive mode — segment boundaries, fire at the exact token
        counts plain decode would hit (decode_i never skips a multiple of
        segment_len, so rank decisions see identical clocks)."""
        slots = self.sched.slots
        mid = [i for i in live if slots[i].mid_prefill]
        decoding = [i for i in live if not slots[i].mid_prefill]
        q_host = {i: min(self.spec_chunk, slots[i].prompt_len
                         - slots[i].prefilled) for i in mid}
        sw = Stopwatch(self.config.time_per_token)
        with ph("decide"):
            self._maybe_decide()
        if self.cache.factored and decoding:
            assert all(self.has_rank[i] for i in decoding), \
                "factored slot would read unseeded kt pages"
        if __debug__:
            for i in decoding:
                # speculative writes start at lens >= prompt_len, past any
                # prefix-shared page (the tail page was COWed at
                # admission) — rollback never rewinds into shared state
                assert self.cache.lens[i] >= self.cache.shared_floor(i), \
                    f"slot {i}: speculative write below shared-page floor"
        with ph("dispatch"):
            self._sync_control()
            active_dec = np.array([s.active and not s.mid_prefill
                                   for s in self.sched.slots])
            self.rank_history.append(
                (self.stats["steps"], self.cache.ranks, active_dec))
            # adaptive draft: the accept cap honours the controller's
            # current effective draft length (>= 1 here — a fully
            # collapsed stream only reaches this path on recovery-probe
            # steps)
            k_eff = (max(self._eff_k, 1) if self.config.adaptive_draft
                     else self.config.draft_k)
            caps = np.ones((self.config.n_slots,), np.int32)
            for i in decoding:
                st = slots[i]
                c = min(k_eff + 1, st.req.max_new - st.n_out)
                if self._decide is not None:
                    c = min(c, self.seg - st.decode_i % self.seg)
                caps[i] = max(c, 1)
            pools, tok, ob, lens, acc, n_emit, emitted = self._step_spec(
                self.params, self.cache.k_pool, self.cache.v_pool,
                self.cache.kt_pool, self.cache.mass_pool,
                self._pt_dev, self.tokens, self._lens_dev, self.cache.ranks,
                self.cache.basis, self._active_dev, self.out_buf,
                self._plen_dev, self._temp_dev, self._topk_dev,
                self._topp_dev, self._seed_dev, self.prompt_buf,
                self.cache.spectra, jnp.asarray(caps), self._eos_dev)
            self._adopt_pools(pools)
            self.tokens, self.out_buf, self._lens_dev = tok, ob, lens
        # the accept fetch doubles as the emission sync: streaming handles
        # need every accepted token this step anyway, so this is the same
        # one-host-sync-per-step budget as the plain path's tok fetch
        with ph("fetch"):
            acc_h, emit_h = jax.device_get((acc, emitted))  # inv-ok[R1]: the one sanctioned per-step sync — the accept/emission fetch doubles as the streaming emit
        dt = sw.stop()
        now_t = time.perf_counter()
        with ph("deliver"):
            for i in live:
                st = slots[i]
                if i in q_host:                   # mid-prefill row
                    q = q_host[i]
                    st.prefilled += q
                    self.cache.lens[i] += q       # host mirror of _lens_dev
                    done_pf = st.prefilled == st.prompt_len
                    self._maybe_snapshot(i, st, done_pf)
                    self.obs.on_prefill_chunk(i, st.req.rid, q, st.prefilled)
                    if done_pf:
                        self._stamp_first_token(i, st, now_t,
                                                now_t - st.admit_s)
                        st.last_tok = int(emit_h[i, 0])
                        self.last_emitted.append(
                            (st.req.rid, 0, int(emit_h[i, 0])))
                        self._insert_prefix(i, st)
                    continue
                a = int(acc_h[i])
                base = st.n_out
                st.decode_i += a
                st.n_out += a
                self.cache.lens[i] += a           # host mirror of _lens_dev
                st.accept_lens.append(a)
                self.obs.on_spec_accept(i, a, int(caps[i]) - 1)
                if self.trace is not None:
                    self.trace.on_step(i, a, dt, accepted=a - 1,
                                       drafted=int(caps[i]) - 1)
                st.last_tok = int(emit_h[i, a - 1])
                self.last_emitted.extend(
                    (st.req.rid, base + t, int(emit_h[i, t]))
                    for t in range(a))
                if dt is not None:
                    st.latencies.extend([dt / a] * a)
                    for _ in range(a):
                        self.obs.on_token_latency(dt / a)
        self.stats["steps"] += 1
        if decoding:
            tot, n_acc, n_drafted = host_accept_stats(
                acc_h, caps, decoding, self.config.draft_k)
            self.stats["spec_steps"] += 1
            self.stats["tokens_decoded"] += tot
            self.stats["spec_tokens"] += tot
            self.stats["spec_accepted"] += n_acc
            self.stats["spec_drafted"] += n_drafted
        if self.config.adaptive_draft and decoding:
            denom = sum(int(caps[i]) - 1 for i in decoding)
            if denom > 0:
                num = sum(int(acc_h[i]) - 1 for i in decoding)
                al = self._DRAFT_EWMA_ALPHA
                self._accept_ewma = ((1.0 - al) * self._accept_ewma
                                     + al * num / denom)
                if self._accept_ewma < self.config.draft_shrink_below:
                    self._eff_k //= 2
                elif self._accept_ewma > self.config.draft_grow_above:
                    self._eff_k = min(self.config.draft_k,
                                      max(1, self._eff_k) * 2)
                self.stats["eff_draft_k"] = self._eff_k
        if mid:
            self.stats["mixed_steps"] += 1
        if self._drift is not None and decoding:
            self._check_drift(decoding)
        self._evict_finished()

    def _evict_finished(self) -> None:
        for i, st in enumerate(self.sched.slots):
            if st.active and self.sched.should_evict(i):
                outputs = np.asarray(self.out_buf[i, :st.n_out]).tolist()  # inv-ok[R1]: one-shot fetch of a finished request's output at eviction, not per-step
                if st.latencies:
                    self.first_token_s.append(st.latencies[0])
                    self.token_latencies.extend(st.latencies[1:])
                if st.accept_lens:
                    self.request_accept_lens[st.req.rid] = list(st.accept_lens)
                if self.trace is not None:
                    self.trace.on_evict(i)
                reason = ("eos" if (st.req.eos_id is not None
                                    and st.last_tok == st.req.eos_id)
                          else "max_new")
                self.obs.on_finish(st.req.rid, i, st.n_out, reason)
                self.sched.evict(i, self.cache.release, outputs)
                self._dirty = True

    def step(self) -> None:
        """One engine iteration: admit -> decide -> fused decode -> evict."""
        self.last_emitted = []
        ph = self.obs.step_phases(self.stats["steps"])
        with ph("admit"):
            self._admit()
        with ph("schedule"):
            live = [i for i, s in enumerate(self.sched.slots) if s.active]
        if live and self.config.speculative and any(
                not self.sched.slots[i].mid_prefill for i in live):
            # at least one row has a token to extend; pure-prefill steps
            # fall through to the mixed step instead — drafting there
            # would run draft_k dead forwards per step for nothing
            spec_now = True
            if self.config.adaptive_draft and self._eff_k == 0:
                # collapsed draft length: decode rides the mixed step
                # (no draft forwards at all); a probe spec step every
                # _DRAFT_PROBE_EVERY iterations keeps sampling the
                # accept signal so a recovered stream grows eff_k back
                spec_now = self._probe_i % self._DRAFT_PROBE_EVERY == 0
                self._probe_i += 1
            if spec_now:
                self._step_live_spec(live, ph)
                live = []
        if live:
            slots = self.sched.slots
            mid = [i for i in live if slots[i].mid_prefill]
            decoding = [i for i in live if not slots[i].mid_prefill]
            # chunk consumed per slot this step (host mirror of the mixed
            # step's in-graph q_lens; 0 for decode rows here)
            q_host = {i: min(self.config.prefill_chunk, slots[i].prompt_len
                             - slots[i].prefilled) for i in mid}
            finishing = [i for i in mid
                         if slots[i].prefilled + q_host[i]
                         == slots[i].prompt_len]
            # the timer starts before the segment decision: tokens decoded
            # in a boundary step really do wait on the decide dispatch
            sw = Stopwatch(self.config.time_per_token)
            with ph("decide"):
                self._maybe_decide()
            if self.cache.factored and decoding:
                # a factored slot's kt pages are only consistent after its
                # first decision re-projects them; decode_i == 0 is always
                # a segment boundary so this holds — keep it explicit in
                # case the decide trigger ever changes. Mid-prefill rows
                # read dense K, so they are exempt.
                assert all(self.has_rank[i] for i in decoding), \
                    "factored slot would read unseeded kt pages"
            with ph("dispatch"):
                self._sync_control()
                active_dec = np.array([s.active and not s.mid_prefill
                                       for s in self.sched.slots])
                self.rank_history.append(
                    (self.stats["steps"], self.cache.ranks, active_dec))
                # a speculative engine never warms the plain decode step
                # (its decode-only shape rides _step_mixed with
                # q_lens == 1), so a collapsed adaptive draft must route
                # through the mixed step too — dispatching _step here
                # would compile in steady state
                use_mixed = bool(mid) or self.config.speculative
                if self._paged_attn[use_mixed]:
                    self._count_paged_read(live, q_host)
                step_fn = self._step_mixed if use_mixed else self._step
                extra = (self.prompt_buf,) if use_mixed else ()
                pools, tok, ob, lens = step_fn(
                    self.params, self.cache.k_pool, self.cache.v_pool,
                    self.cache.kt_pool, self.cache.mass_pool,
                    self._pt_dev, self.tokens, self._lens_dev,
                    self.cache.ranks, self.cache.basis, self._active_dev,
                    self.out_buf, self._plen_dev, self._temp_dev,
                    self._topk_dev, self._topp_dev, self._seed_dev, *extra)
                self._adopt_pools(pools)
                self.tokens, self.out_buf, self._lens_dev = tok, ob, lens
                if self.config.time_per_token:
                    jax.block_until_ready(tok)  # inv-ok[R1]: opt-in timing mode deliberately syncs to attribute per-step latency
            dt = sw.stop()
            emitting = decoding + finishing
            need_tok = (self._stream_sync and emitting) or any(
                self.sched.slots[i].req.eos_id is not None for i in emitting)
            with ph("fetch"):
                tok_host = np.asarray(tok[:, 0]) if need_tok else None  # inv-ok[R1]: the plain path's one sanctioned per-step sync — EOS detection and streaming need this step's token
            now_t = time.perf_counter()
            with ph("deliver"):
                for i in live:
                    st = self.sched.slots[i]
                    if i in q_host:               # mid-prefill row
                        q = q_host[i]
                        st.prefilled += q
                        self.cache.lens[i] += q   # host mirror of _lens_dev
                        done_pf = st.prefilled == st.prompt_len
                        self._maybe_snapshot(i, st, done_pf)
                        self.obs.on_prefill_chunk(i, st.req.rid, q,
                                                  st.prefilled)
                        if done_pf:
                            self._stamp_first_token(i, st, now_t,
                                                    now_t - st.admit_s)
                            if tok_host is not None:
                                st.last_tok = int(tok_host[i])
                            self._insert_prefix(i, st)
                        continue
                    st.decode_i += 1
                    st.n_out += 1
                    self.cache.lens[i] += 1       # host mirror of _lens_dev
                    if self.trace is not None:
                        self.trace.on_step(i, 1, dt)
                    if tok_host is not None:
                        st.last_tok = int(tok_host[i])
                    if dt is not None:
                        st.latencies.append(dt)
                        self.obs.on_token_latency(dt)
                if tok_host is not None:
                    self.last_emitted.extend(
                        (self.sched.slots[i].req.rid,
                         self.sched.slots[i].n_out - 1, int(tok_host[i]))
                        for i in emitting)
            self.stats["steps"] += 1
            self.stats["tokens_decoded"] += len(decoding)
            if mid:
                self.stats["mixed_steps"] += 1
            if self._drift is not None and decoding:
                self._check_drift(decoding)
            self._evict_finished()
        self.now += 1
        live = [st for st in self.sched.slots if st.active]
        self.obs.on_step(queued=len(self.sched.pending), live=len(live),
                         kv_positions=sum(st.prefilled + st.n_out
                                          for st in live))

    def _count_paged_read(self, live: List[int], q_host: Dict[int, int]
                          ) -> None:
        """``serve.attn.*`` for a plain fused step that runs the paged
        kernel (the speculative step is not counted): the pages its
        blocks cover, each live row up to its kv_end in whole blocks
        (``pages_read``), against every slot's whole page table
        (``pages_spanned``), both reckoned from the host's lengths."""
        bp, ps = self._attn_block
        ends = self.cache.lens[live] + [q_host.get(i, 1) for i in live]
        self.stats["attn.paged_steps"] += 1
        self.stats["attn.pages_read"] += int((-(-ends // (bp * ps))).sum()
                                             * bp)
        self.stats["attn.pages_spanned"] += self.cache.page_table.size

    def run(self, max_steps: Optional[int] = None) -> Dict:
        """Drive the loop until every request finished. Returns
        {rid: np.ndarray of generated tokens}."""
        sw = Stopwatch()
        steps = 0
        while not self.sched.done():
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        jax.block_until_ready(self.out_buf)  # inv-ok[R1]: end-of-run drain before the wall clock is read
        self.stats["decode_s"] += sw.stop()
        return self.results()

    def results(self) -> Dict[int, np.ndarray]:
        return {req.rid: np.asarray(out, np.int32)
                for req, out in self.sched.finished}

    def ranks_per_step(self) -> List[np.ndarray]:
        """Host copy of the per-step (ranks, active) record; -1 marks dead
        lanes, mid-prefill lanes AND full-rank decode (rank mode 'off'),
        where the cache's r_max placeholder is not a real bucket."""
        if self.cfg.rank.mode == "off":
            return [np.full(a.shape, -1) for _, _, a in self.rank_history]
        return [np.where(a, np.asarray(r), -1)
                for _, r, a in self.rank_history]
