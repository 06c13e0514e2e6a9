"""Shared model components: RoPE (incl. M-RoPE), masks, caches."""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def scan_or_unroll(body, carry, xs, unroll: bool = False):
    """lax.scan, or a python-unrolled equivalent.

    The unrolled form exists for the dry-run calibration: XLA's
    cost_analysis counts a while-loop body ONCE, so roofline FLOPs/bytes are
    extracted from small unrolled depths and extrapolated linearly in L
    (see benchmarks/roofline.py)."""
    if not unroll:
        return jax.lax.scan(body, carry, xs)
    length = jax.tree_util.tree_leaves(xs)[0].shape[0]
    ys = []
    for i in range(length):
        xi = jax.tree_util.tree_map(lambda a: a[i], xs)
        carry, y = body(carry, xi)
        ys.append(y)
    if ys and jax.tree_util.tree_leaves(ys[0]):
        ys = jax.tree_util.tree_map(lambda *z: jnp.stack(z), *ys)
    else:
        ys = ys[0] if ys else None
    return carry, ys


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10000.0) -> jnp.ndarray:
    """x: (b, s, h, d); positions: (b, s) int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (d/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (b, s, d/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jnp.ndarray, positions3: jnp.ndarray, sections: Tuple[int, ...],
                theta: float = 10000.0) -> jnp.ndarray:
    """Qwen2-VL M-RoPE. x: (b, s, h, d); positions3: (b, 3, s) for (t, h, w).

    ``sections`` gives the number of *frequency pairs* per position stream and
    must sum to d/2.
    """
    d = x.shape[-1]
    assert sum(sections) == d // 2, (sections, d)
    freqs = rope_freqs(d, theta)                       # (d/2,)
    # build per-frequency position selection: first sections[0] pairs follow t,
    # next sections[1] follow h, last follow w.
    sel = jnp.concatenate([jnp.full((s,), i, jnp.int32) for i, s in enumerate(sections)])
    pos = jnp.take_along_axis(
        positions3.astype(jnp.float32),                # (b, 3, s)
        jnp.broadcast_to(sel[None, :, None], (x.shape[0], d // 2, x.shape[1])),
        axis=1,
    )                                                  # (b, d/2, s)
    angles = jnp.transpose(pos, (0, 2, 1)) * freqs     # (b, s, d/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def causal_mask(q_len: int, kv_len: int, q_offset=0) -> jnp.ndarray:
    """Boolean (q_len, kv_len) mask; True = attend."""
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    k_pos = jnp.arange(kv_len)[None, :]
    return k_pos <= q_pos


def repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """(b, s, kv, d) -> (b, s, kv*n_rep, d) for GQA."""
    if n_rep == 1:
        return x
    b, s, kv, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, kv, n_rep, d)).reshape(b, s, kv * n_rep, d)


def group_q_heads(x: jnp.ndarray, hkv: int) -> jnp.ndarray:
    """(b, s, hq, d) -> (b, n_rep * s, hkv, d) for a per-kv-group
    contraction: kv head g gets the rows of its q heads g * n_rep + n
    (repeat_kv's order), row n * s + i for position i, so it is read once
    for all of them. With hkv == hq nothing moves."""
    b, s, hq, d = x.shape
    n_rep = hq // hkv
    return x.reshape(b, s, hkv, n_rep, d).transpose(0, 3, 1, 2, 4).reshape(
        b, n_rep * s, hkv, d)


def ungroup_q_heads(x: jnp.ndarray, s: int) -> jnp.ndarray:
    """The inverse of :func:`group_q_heads`: (b, n_rep * s, hkv, d) ->
    (b, s, hq, d)."""
    b, m, hkv, d = x.shape
    n_rep = m // s
    return x.reshape(b, n_rep, s, hkv, d).transpose(0, 2, 3, 1, 4).reshape(
        b, s, hkv * n_rep, d)


def kv_group_mean(w: jnp.ndarray, hkv: int) -> jnp.ndarray:
    """(..., hq, K) per-q-head key weights -> (..., hkv, K) mean per kv
    group. The inverse reduction of :func:`repeat_kv`: consecutive q heads
    share a kv head, so every per-key attention-mass consumer (serve
    prefill seed, fused decode accumulator, lowrank prefill basis) reduces
    through here and stays consistent with one GQA head layout."""
    hq, K = w.shape[-2], w.shape[-1]
    return w.reshape(*w.shape[:-2], hkv, hq // hkv, K).mean(-2)


def make_kv_cache(batch: int, max_len: int, n_kv: int, head_dim: int, dtype) -> dict:
    return {
        "k": jnp.zeros((batch, max_len, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, max_len, n_kv, head_dim), dtype),
        "len": jnp.zeros((), jnp.int32),
    }


def cache_update(cache: dict, k_new: jnp.ndarray, v_new: jnp.ndarray) -> dict:
    """Append k/v (b, s_new, kv, d) at cache['len']."""
    idx = cache["len"]
    k = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype), (0, idx, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype), (0, idx, 0, 0))
    return {"k": k, "v": v, "len": idx + k_new.shape[1]}
