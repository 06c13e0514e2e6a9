"""Repo-specific knowledge feeding the generic rule visitors.

Everything the AST cannot see on its own lives here, in one reviewed
place: the step-loop entry points and control-plane stops (R1), the
dynamic attribute hops the call graph needs (``self.fns.
decode_step_paged`` is a model-registry lookup, ``self.core.step`` a
composition edge), the shared-state -> owning-lock map (R3), and the
donation rules whose ``donate_argnums`` are computed at runtime
(backend-conditional tuples the indexer cannot fold) (R4).

Rules also honour *inline* declarations so fixtures and future classes
can self-register without editing this file:

* ``_inv_locks_ = {"attr": ("lockname", ...)}`` class attribute — R3;
* literal ``donate_argnums`` tuples on ``jax.jit`` bindings — R4
  (picked up by the indexer, no registry entry needed).
"""
from __future__ import annotations

from dataclasses import dataclass, field

# --------------------------------------------------------------------------
# R1 — host-sync: step-loop entry points, control-plane stops, dynamic hops
# --------------------------------------------------------------------------

# (path suffix, qualname): the host-side fused-step loop.  The graph is
# built by reachability from these — not a hardcoded file list.
HOST_ENTRIES: tuple[tuple[str, str], ...] = (
    ("serve/engine.py", "ServeEngine.step"),
    ("serve/engine.py", "ServeEngine.run"),
    ("serve/api.py", "Engine.step"),
    ("serve/api.py", "Engine.run"),
    ("serve/frontend.py", "FrontEnd._loop"),
)

# Control-plane boundaries the host-sync rule does not cross, with the
# reason each is exempt (admission and warmup legitimately block).
HOST_STOPS: dict[tuple[str, str], str] = {
    ("serve/engine.py", "ServeEngine._admit"):
        "admission is control-plane: it stages prompts on device and "
        "runs no model work",
    ("serve/engine.py", "ServeEngine.warmup"):
        "warmup exists to absorb compiles and syncs before serving",
    ("serve/engine.py", "ServeEngine.reset"):
        "reset tears the serving state down; latency is irrelevant",
    ("serve/api.py", "Engine.warmup"):
        "warmup exists to absorb compiles and syncs before serving",
    ("serve/api.py", "Engine.reset"):
        "reset tears the serving state down; latency is irrelevant",
}

# Dynamic attribute hops: ``self.<a>.<b>(...)`` edges the resolver
# cannot derive.  Keyed by the last one or two dotted parts.
ATTR_TARGETS: dict[str, tuple[str, str]] = {
    # model-registry indirection: the fused step's decode body
    "fns.decode_step_paged": ("models/transformer.py", "decode_step_paged"),
    # composition edges across the serving layers
    "core.step": ("serve/engine.py", "ServeEngine.step"),
    "core.run": ("serve/engine.py", "ServeEngine.run"),
    "engine.step": ("serve/api.py", "Engine.step"),
    # trace-recorder hooks off the step loop (engine.trace is None unless
    # EngineConfig.record_traces is set)
    "trace.on_decision": ("serve/traces.py", "TraceRecorder.on_decision"),
    "trace.on_step": ("serve/traces.py", "TraceRecorder.on_step"),
    "trace.on_evict": ("serve/traces.py", "TraceRecorder.on_evict"),
    # observability hooks off the step loop (always constructed; pure
    # host Python — see repro.obs.core's module docstring)
    "obs.step_phases": ("obs/core.py", "Observability.step_phases"),
    "obs.stats_view": ("obs/core.py", "Observability.stats_view"),
    "obs.reset_run": ("obs/core.py", "Observability.reset_run"),
    "obs.on_admit": ("obs/core.py", "Observability.on_admit"),
    "obs.on_first_token": ("obs/core.py", "Observability.on_first_token"),
    "obs.on_finish": ("obs/core.py", "Observability.on_finish"),
    "obs.on_decide": ("obs/core.py", "Observability.on_decide"),
    "obs.on_step": ("obs/core.py", "Observability.on_step"),
    "obs.on_drift": ("obs/core.py", "Observability.on_drift"),
    "obs.on_prefill_chunk": ("obs/core.py", "Observability.on_prefill_chunk"),
    "obs.on_spec_accept": ("obs/core.py", "Observability.on_spec_accept"),
    "obs.on_token_latency": ("obs/core.py", "Observability.on_token_latency"),
    "obs.set_prefix_size": ("obs/core.py", "Observability.set_prefix_size"),
    "obs.record_event": ("obs/core.py", "Observability.record_event"),
    "obs.flight_dump": ("obs/core.py", "Observability.flight_dump"),
    "obs.rank_telemetry": ("obs/core.py", "Observability.rank_telemetry"),
}


# --------------------------------------------------------------------------
# R3 — lock discipline
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class LockRule:
    """Shared attributes of one class and the lock(s) that own them.

    * ``locks``: any-of — a mutation under any listed lock is fine;
    * ``attrs``: ``self.<attr>`` chains whose stores must hold a lock;
    * ``mutator_methods``: method names that count as mutation when
      called on a registered attr (``self.cache.allocate(...)``);
    * ``assume_held``: methods whose bodies run with the lock held —
      every intra-class call site is checked to actually hold it;
    * ``external``: methods whose mutations are serialised by
      something outside this class; the justification is mandatory
      and rendered in the report.
    """

    path_suffix: str
    cls: str
    locks: tuple[str, ...]
    attrs: tuple[str, ...]
    mutator_methods: tuple[str, ...] = ()
    assume_held: tuple[str, ...] = ()
    external: dict[str, str] = field(default_factory=dict)


_STEP_LOOP_WHY = (
    "step-loop method: the stepping thread is the sole driver by "
    "contract, serialised against submit/cancel by "
    "repro.serve.api.Engine._step_lock (and FrontEnd's single thread)"
)

LOCK_RULES: tuple[LockRule, ...] = (
    LockRule(
        path_suffix="serve/engine.py",
        cls="ServeEngine",
        locks=("_lock",),
        attrs=("sched", "cache", "prefix", "_hits", "_snaps",
               "_spectra_pending", "last_emitted", "request_first_tok_t"),
        mutator_methods=(
            # scheduler
            "submit", "cancel_pending", "evict", "admit",
            # paged KV cache
            "allocate", "release", "retain", "unref", "copy_page",
            "write_prefill",
            # prefix radix tree (match/touch_path move the LRU clock)
            "insert", "evict_lru", "touch_path", "match",
        ),
        assume_held=("_admit_locked", "_can_allocate", "_apply_prefix_hit"),
        external={
            "step": _STEP_LOOP_WHY,
            "_adopt_pools": _STEP_LOOP_WHY,
            "_step_live_spec": _STEP_LOOP_WHY,
            "_evict_finished": _STEP_LOOP_WHY,
            "_maybe_decide": _STEP_LOOP_WHY,
            "_maybe_snapshot": _STEP_LOOP_WHY,
            "_insert_prefix": _STEP_LOOP_WHY,
            "_stamp_first_token": _STEP_LOOP_WHY,
            "_check_drift": _STEP_LOOP_WHY,
            "_sync_control": _STEP_LOOP_WHY,
            "warmup": _STEP_LOOP_WHY,
            "run": _STEP_LOOP_WHY,
            "_reset_state": "called from __init__ and from reset() "
                            "(which holds _lock)",
        },
    ),
    LockRule(
        path_suffix="serve/api.py",
        cls="Engine",
        locks=("_submit_lock", "_step_lock"),
        attrs=("_handles", "_next_rid", "_streaming", "_finished_seen"),
    ),
    LockRule(
        path_suffix="serve/api.py",
        cls="RequestHandle",
        locks=("_cv",),
        attrs=("_toks", "_result", "ttft_s", "done_s", "cancelled",
               "_stopped"),
    ),
    LockRule(
        path_suffix="serve/frontend.py",
        cls="Router",
        locks=("_lock",),
        attrs=("_rr", "routed", "route_kinds"),
        assume_held=("_pick",),
    ),
    LockRule(
        path_suffix="serve/frontend.py",
        cls="FrontEnd",
        locks=("_idle_cv",),
        attrs=("_error",),
        external={
            "_loop": "the stepping thread is the sole writer; readers "
                     "(_raise_if_dead) tolerate one poll of staleness",
        },
    ),
)


# --------------------------------------------------------------------------
# R4 — donation safety
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class DonationRule:
    """Calls through ``self.<binding>``/``<binding>`` donate the listed
    positional args.  These mirror jit bindings whose donate_argnums
    are backend-conditional at runtime; the static rule assumes the
    worst case (donation active)."""

    path_suffix: str
    bindings: tuple[str, ...]
    positions: tuple[int, ...]


DONATION_RULES: tuple[DonationRule, ...] = (
    # ServeEngine.__init__: jax.jit(self._step*_impl, donate_argnums=
    # (1, 2, 3, 4, 11)) — k/v/kt/mass pools + out_buf
    DonationRule("serve/engine.py",
                 ("_step", "_step_mixed", "_step_spec"),
                 (1, 2, 3, 4, 11)),
    # policy.make_decide_fn: decide(..., donate_argnums=(2, 6, 7)) —
    # kt_pool, basis, spectra
    DonationRule("serve/engine.py", ("_decide",), (2, 6, 7)),
)

# Calls that adopt/overwrite donated buffers: a call to the method
# counts as reassignment of the listed expressions.
DONATION_REASSIGNERS: dict[str, tuple[str, ...]] = {
    "_adopt_pools": ("self.cache.k_pool", "self.cache.v_pool",
                     "self.cache.kt_pool", "self.cache.mass_pool"),
}
