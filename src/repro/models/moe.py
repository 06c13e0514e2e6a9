"""Mixture-of-Experts FFN with sort-based grouped dispatch.

Static-shape, GSPMD-friendly: tokens' (token, expert) pairs are sorted by
expert id, placed into a fixed-capacity (E, C, d) buffer (overflow dropped),
run through batched expert SwiGLUs (one einsum over the expert dim — the
expert dim is sharded over the `model` mesh axis => expert parallelism), and
scattered back with gate weighting. Supports shared experts (DeepSeek-V3) and
a load-balancing auxiliary loss.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro import nn
from repro.configs.base import ModelConfig


def init_moe(cfg: ModelConfig, rng, dtype) -> Dict[str, jnp.ndarray]:
    assert cfg.moe is not None
    m, d = cfg.moe, cfg.d_model
    ks = nn.split_keys(rng, 5)
    p = {
        "router": nn.dense_init(ks[0], d, m.num_experts, jnp.float32, scale=0.02),
        # stacked expert weights: (E, d, f) / (E, f, d)
        "w_gate": jax.vmap(lambda k: nn.dense_init(k, d, m.d_expert, dtype))(
            jax.random.split(ks[1], m.num_experts)),
        "w_up": jax.vmap(lambda k: nn.dense_init(k, d, m.d_expert, dtype))(
            jax.random.split(ks[2], m.num_experts)),
        "w_down": jax.vmap(lambda k: nn.dense_init(k, m.d_expert, d, dtype))(
            jax.random.split(ks[3], m.num_experts)),
    }
    if m.num_shared_experts:
        f = m.d_shared * m.num_shared_experts
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": nn.dense_init(k1, d, f, dtype),
            "w_up": nn.dense_init(k2, d, f, dtype),
            "w_down": nn.dense_init(k3, f, d, dtype),
        }
    return p


def moe_ffn(cfg: ModelConfig, p: Dict[str, jnp.ndarray], x: jnp.ndarray
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x: (b, s, d) -> (y, aux{'aux_loss'})."""
    m = cfg.moe
    b, s, d = x.shape
    T = b * s
    E, K = m.num_experts, m.top_k
    C = max(int(T * K / E * m.capacity_factor), 1)

    xf = x.reshape(T, d)
    # router: bf16 operands with f32 accumulation — a full f32 copy of xf
    # would get reused by XLA as the dispatch-gather source, running the
    # (T*K, d) 240 GB/op chain in f32 (EXPERIMENTS.md §Perf H5)
    logits = jnp.einsum("td,de->te", xf, p["router"].astype(x.dtype),
                        preferred_element_type=jnp.float32)
    # barrier: keep the gather source pinned to the bf16 value
    xf = jax.lax.optimization_barrier(xf)
    probs = jax.nn.softmax(logits, axis=-1)                       # (T, E)
    gates, idx = jax.lax.top_k(probs, K)                          # (T, K)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)

    # ---- load-balancing auxiliary loss (Switch-style) ----
    me = jnp.mean(probs, axis=0)                                  # (E,)
    ce = jnp.mean(jax.nn.one_hot(idx, E).sum(1), axis=0)          # (E,)
    aux_loss = m.router_aux_coef * E * jnp.sum(me * ce)

    # ---- sort-based dispatch ----
    flat_e = idx.reshape(-1)                                      # (T*K,)
    order = jnp.argsort(flat_e)                                   # stable
    se = flat_e[order]                                            # sorted experts
    tok = order // K                                              # source token
    counts = jax.ops.segment_sum(jnp.ones_like(flat_e), flat_e, num_segments=E)
    starts = jnp.cumsum(counts) - counts                          # exclusive
    pos = jnp.arange(T * K) - starts[se]                          # slot in expert
    keep = pos < C
    slot = jnp.where(keep, se * C + jnp.clip(pos, 0, C - 1), E * C)  # E*C = trash

    buf = jnp.zeros((E * C + 1, d), x.dtype).at[slot].set(xf[tok])
    h = buf[:E * C].reshape(E, C, d)

    # ---- batched expert SwiGLU (expert dim shardable over 'model') ----
    g = jnp.einsum("ecd,edf->ecf", h, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", h, p["w_up"].astype(x.dtype))
    o = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                   p["w_down"].astype(x.dtype))

    # ---- combine ----
    # NB: keep the (T*K, d) gather/scatter chain in the activation dtype —
    # an f32 gate multiply here promotes a 240 GB/op fusion chain to f32 on
    # the deepseek-v3 train cell (EXPERIMENTS.md §Perf H5)
    o_slots = o.reshape(E * C, d)
    gate_sorted = (gates.reshape(-1)[order] * keep).astype(x.dtype)
    contrib = o_slots[jnp.clip(slot, 0, E * C - 1)] * gate_sorted[:, None]
    y = jnp.zeros((T, d), x.dtype).at[tok].add(contrib)

    if "shared" in p:
        sp = p["shared"]
        y = y + nn.swiglu(xf, sp["w_gate"], sp["w_up"], sp["w_down"])
    return y.reshape(b, s, d), {"aux_loss": aux_loss}


def moe_ffn_dense_fallback(cfg: ModelConfig, p, x):
    """Reference (oracle) implementation: every expert on every token, then
    gate-weighted sum. O(T*E) compute — used only in tests."""
    m = cfg.moe
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, m.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    g = jnp.einsum("td,edf->tef", xf, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("td,edf->tef", xf, p["w_up"].astype(x.dtype))
    o = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u, p["w_down"].astype(x.dtype))
    w = jnp.zeros(probs.shape, x.dtype)
    w = jax.vmap(lambda wi, ii, gi: wi.at[ii].set(gi.astype(x.dtype)))(w, idx, gates)
    y = jnp.einsum("te,ted->td", w, o)
    if "shared" in p:
        sp = p["shared"]
        y = y + nn.swiglu(xf, sp["w_gate"], sp["w_up"], sp["w_down"])
    return y.reshape(b, s, d)
