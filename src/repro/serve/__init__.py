"""Continuous-batching serving engine (paper section 4.5.2 at scale).

- config:    EngineConfig, the one engine-construction config (shared
             by api.Engine and engine.ServeEngine, validated once).
- api:       the public surface — EngineConfig + SamplingParams +
             Engine.submit(prompt, params) -> RequestHandle with
             incremental token streaming and per-request TTFT.
- kv_cache:  slot-paged KV cache — a shared page pool + per-slot page
             tables, per-slot valid lengths / rank buckets / eigenbasis.
- scheduler: request queue, admission (free slots + page reservation,
             chunked prompts tracked mid-prefill), eviction.
- policy:    slot-indexed segment-level rank decision + eigenbasis refresh
             (device-resident, no host syncs).
- prefix:    shared-prefix KV reuse — a token-level radix tree over
             page-granularity prefixes with refcounted page sharing,
             exact attention-mass snapshots, LRU eviction and
             copy-on-write of partially-filled shared tail pages.
- engine:    the step loop core — one fused decode executable over all
             live slots with per-row kv_len, per-row rank, and chunked
             prefill interleaved into the same step.
- frontend:  the async front door — a background stepping thread per
             engine (FrontEnd), awaitable/streaming handles with
             cancellation, and a Router that load-balances N replicas
             by queue depth with prefix-cache affinity, configured
             through one FleetConfig.
- traces:    serving-trace recording (per-segment rank-decision features
             + outcomes, versioned npz shards) and the replay reader the
             offline policy trainer (repro.train.serve_policy) consumes.
- workloads: deterministic named scenario generators (bursty arrivals,
             long-context, shared-prefix chat, mixed sampling) used for
             trace generation and replay benchmarking.
"""
from repro.serve.api import (Engine, EngineConfig, EngineStopped,
                             RequestHandle, SamplingParams, make_engine)
from repro.serve.engine import ServeEngine
from repro.serve.frontend import FleetConfig, FrontEnd, Router
from repro.serve.kv_cache import PagedKVCache
from repro.serve.prefix import PrefixCache, RadixNode
from repro.serve.scheduler import Request, Scheduler
from repro.serve.traces import (TRACE_SCHEMA_VERSION, TraceReader,
                                TraceRecorder)
from repro.serve.workloads import WorkloadSpec, make_workload, workload_names

__all__ = ["Engine", "EngineConfig", "EngineStopped", "RequestHandle",
           "SamplingParams", "make_engine", "ServeEngine", "FleetConfig",
           "FrontEnd", "Router", "PagedKVCache", "PrefixCache",
           "RadixNode", "Request", "Scheduler", "TRACE_SCHEMA_VERSION",
           "TraceReader", "TraceRecorder", "WorkloadSpec", "make_workload",
           "workload_names"]
