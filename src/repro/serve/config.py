"""The one engine-construction config: :class:`EngineConfig`.

``repro.serve.api.Engine`` and the core ``repro.serve.engine.ServeEngine``
both take it as ``config=``; ``EngineConfig.__post_init__`` is the one
place its fields are validated. Public as ``repro.serve.EngineConfig``
and ``repro.serve.api.EngineConfig``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import TYPE_CHECKING, Optional, Union

if TYPE_CHECKING:
    from repro.serve.traces import TraceRecorder


@dataclass(frozen=True)
class EngineConfig:
    """Engine-level serving knobs (one compile scope).

    ``prefill_chunk``: prompt tokens consumed per fused step while a
    stream is mid-prefill (chunked prefill interleaved into decode).
    ``sampling=False`` compiles the lean greedy-only step (requests with
    temperature/top_k/top_p then fail fast at submit); ``nucleus=True``
    additionally compiles the top-p cut — a full-vocab softmax + sort in
    every fused step, so leave it off unless streams use ``top_p < 1``
    (such requests fail fast on a nucleus=False engine).
    ``prefix_cache=True`` turns on shared-prefix KV reuse
    (``repro.serve.prefix``): finished prompts stay cached in a radix
    tree, and a request whose prompt starts with a cached prefix shares
    those pages and prefills only from the divergence point — token
    parity with cold admission is preserved. Run ``prefill_chunk`` as a
    multiple of ``page_size`` for a reuse point at every page.
    ``prefix_pages`` sizes the extra pool headroom kept for cached
    prefixes (default: one extra slot-set of pages).
    ``speculative=True`` turns on low-rank self-speculative decoding
    (``repro.serve.spec``): each fused step drafts ``draft_k`` tokens
    ahead reading the factor cache at roughly ``draft_rank_frac`` of
    each row's live rank, verifies all of them in one chunked step at
    the full current rank, and accepts the longest matching prefix —
    token-identical to plain decode (greedy and seeded sampling), only
    faster. ``snapshot_every`` throttles prefix-cache mass snapshots to
    every k-th page boundary (probe / match fall back to the nearest
    earlier snapshot).
    ``adaptive_draft=True`` (speculative engines only) lets an EWMA of
    the accept fraction shrink the effective draft length when accept
    runs collapse (below ``draft_shrink_below``) and restore it when
    they recover (above ``draft_grow_above``); a fully collapsed stream
    skips the draft forwards entirely, and ``stats['eff_draft_k']``
    exposes the live value. Token streams stay exactly identical to
    plain decode either way. ``record_traces=<dir>`` (or a shared
    :class:`repro.serve.traces.TraceRecorder`) hooks the trace recorder
    into the rank-decision path: per-segment decision features and
    outcomes land in versioned npz shards for offline policy training
    (``repro.train.serve_policy``); call ``engine.core.trace.flush()``
    when serving is done.

    ``obs_trace=True`` turns on :mod:`repro.obs` span/phase tracing
    (per-request spans + per-step phase timeline, exported as Chrome
    trace-event JSON via ``engine.obs.chrome_trace()``); the metrics
    registry itself is always on and costs the loop nothing beyond the
    host-side counter adds it already did. ``flight_dir=<dir>`` enables
    flight-recorder dumps: a bounded ring of recent engine events is
    written there on step exceptions, front-end shutdown and
    ``reset()`` with requests still in flight."""
    n_slots: int = 4
    max_len: int = 256
    page_size: int = 16
    segment_len: Optional[int] = None
    max_new_cap: int = 256
    prefill_chunk: int = 16
    use_kernel: bool = False
    drift_threshold: Optional[float] = None
    factor_cache: Optional[bool] = None
    prefix_cache: bool = False
    prefix_pages: Optional[int] = None
    time_per_token: bool = False
    sampling: bool = True
    nucleus: bool = False
    top_k_cap: int = 64
    speculative: bool = False
    draft_k: int = 4
    draft_rank_frac: float = 0.25
    snapshot_every: int = 1
    adaptive_draft: bool = False
    draft_shrink_below: float = 0.35
    draft_grow_above: float = 0.6
    record_traces: Union[str, TraceRecorder, None] = None
    obs_trace: bool = False
    flight_dir: Optional[str] = None
    flight_capacity: int = 256

    def __post_init__(self):
        if self.flight_capacity < 1:
            raise ValueError(f"flight_capacity must be >= 1, got "
                             f"{self.flight_capacity}")
        if self.prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {self.prefill_chunk}")
        if self.max_len < 1 or self.n_slots < 1 or self.page_size < 1:
            raise ValueError("n_slots/max_len/page_size must be >= 1")
        if self.nucleus and not self.sampling:
            raise ValueError("nucleus (top-p) requires sampling=True")
        if self.speculative and self.draft_k < 1:
            raise ValueError(f"draft_k must be >= 1, got {self.draft_k}")
        if not 0.0 < self.draft_rank_frac <= 1.0:
            raise ValueError(f"draft_rank_frac must be in (0, 1], got "
                             f"{self.draft_rank_frac}")
        if self.snapshot_every < 1:
            raise ValueError(f"snapshot_every must be >= 1, got "
                             f"{self.snapshot_every}")
        if self.adaptive_draft and not self.speculative:
            raise ValueError("adaptive_draft requires speculative=True")
