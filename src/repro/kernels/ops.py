"""jit'd public wrappers around the Pallas kernels.

On CPU (this container) the kernels execute in interpret mode for
correctness validation; on TPU they compile through Mosaic. The XLA einsum
path in repro.models.attention remains the lowering used by the dry-run
(see DESIGN.md section 3 — kernels are the TPU runtime hot-spot layer)."""
from __future__ import annotations

import jax

from repro.kernels.decode_attn import flash_decode
from repro.kernels.lowrank_flash import lowrank_flash
from repro.kernels.paged_attn import paged_decode_attention


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def flash_attention(q, k, v, *, scale: float, causal: bool = True,
                    block_q: int = 128, block_k: int = 128,
                    q_offset: int = 0, interpret=None):
    """Flash attention over (b, h, s, d) layouts; d may be a truncated rank.
    See repro.kernels.ref.flash_ref for exact semantics."""
    if interpret is None:
        interpret = _on_cpu()
    return lowrank_flash(q, k, v, scale=scale, causal=causal,
                         block_q=block_q, block_k=block_k,
                         q_offset=q_offset, interpret=interpret)


def decode_attention(q, k, v, kv_len, *, scale: float, block_k: int = 512,
                     interpret=None, return_probs: bool = False,
                     q_start=None, q_lens=None):
    """Flash-decode; kv_len may be () or per-row (b,). ``q`` is (b, hq, r)
    for one decode token or (b, hq, C, r) for a per-row chunk of C query
    tokens (chunked prefill interleaved into the fused serve step, or a
    speculative verify block) with ``q_start`` the per-row cache position
    of the first query and ``q_lens`` the optional per-row valid query
    count (padding queries come out exactly zero). ``return_probs`` also
    returns the normalised attention rows (b, hq, [C,] M) for the serving
    engine's attention-mass accumulator.
    See repro.kernels.ref.decode_ref / decode_chunk_ref."""
    if interpret is None:
        interpret = _on_cpu()
    return flash_decode(q, k, v, kv_len, scale=scale, block_k=block_k,
                        interpret=interpret, return_probs=return_probs,
                        q_start=q_start, q_lens=q_lens)


def paged_attention(q, k_pool, v_pool, layer, page_table, kv_end, q_start,
                    q_lens, *, scale: float, return_mass: bool = False,
                    interpret=None):
    """The fused serve step's attention read straight from the page pools:
    q (ns, C, hq, dh) against each row's pages of ``k_pool``/``v_pool``
    (L, P, page_size, hkv * dh) at ``layer``, up to the row's ``kv_end``.
    ``return_mass`` also returns the per-key attention mass (ns, hkv, M).
    See repro.kernels.paged_attn."""
    if interpret is None:
        interpret = _on_cpu()
    return paged_decode_attention(q, k_pool, v_pool, layer, page_table,
                                  kv_end, q_start, q_lens, scale=scale,
                                  return_mass=return_mass,
                                  interpret=interpret)
