"""Pallas TPU attention for the fused serve step that reads the K/V page
pools in place, through the page table, and stops at each row's kv_len.

The serving cache (repro.serve.kv_cache) stores each layer's keys and
values as pages of ``(page_size, hkv * dh)`` rows: one row per position,
every kv head side by side. An XLA attention over ``(M, hkv, dh)`` has to
gather every page of every slot and re-tile those rows, whatever the live
lengths. This kernel instead DMAs a row's live pages, a block of a few
pages at a time, from the whole pool left in HBM into VMEM, and splits
the heads out of the 128-lane tiles there. Blocks at or past
``ceil(kv_end / block)`` are never read.

One grid step per cache row (slot). Within it, one loop over the row's
live blocks: first every K block (scores into a VMEM strip, running max
per query), then every V block (probabilities against the final max, the
row sum, and P.V), double-buffered so that the next block's pages are in
flight while this one is computed. The softmax is therefore normalised
exactly once, and the normalised probabilities come from the strip
without a second score pass.

Queries arrive grouped per kv head: kv head ``g`` serves its ``n_rep`` q
heads ``g * n_rep + j`` (repeat_kv's order), read once for all of them.
Where ``dh`` is below the 128-lane tile, ``128 // dh`` kv heads share a
tile, and the queries of all of them are stacked against it: each head's
queries are zero outside its own lanes, so one contraction against the
tile gives every head's scores exactly. Each query row's output is then
its own head's lanes of P.V, taken outside the kernel.

Masks are those of :func:`repro.kernels.decode_attn.flash_decode`: query
``c`` of a row sees key ``k`` when ``k <= q_start + c``, ``k < kv_end``
and ``c < q_len``. A row with ``q_len == 0`` or ``kv_end == 0`` (a dead
lane) reads nothing and returns zeros, as does a masked query.

``return_mass`` also returns, per row, the normalised probabilities
summed over the row's valid queries and averaged over each kv head's
q heads, ``(ns, hkv, M)`` float32, zero past ``kv_end``: the serving
engine's per-key attention mass (the weighted-Gram input of the next
segment decision). Everything is computed in float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
# positions a block covers: one lane tile of scores
BLOCK_POSITIONS = 128
# what the kernel may hold in VMEM (the v5e scoped default is 16 MiB)
VMEM_BUDGET = 12 * 2 ** 20


def block_pages(pages_per_slot: int, page_size: int) -> int:
    """Pages one kernel block covers: the most pages, up to
    ``BLOCK_POSITIONS`` positions, that divide a slot's page count."""
    bp = max(1, min(pages_per_slot, BLOCK_POSITIONS // page_size))
    while pages_per_slot % bp:
        bp -= 1
    return bp


def _heads_per_tile(hkv: int, dh: int) -> int:
    return math.gcd(hkv, LANES // dh) if dh < LANES else 1


def _vmem_bytes(*, chunk: int, hq: int, hkv: int, dh: int, max_len: int,
               page_size: int, pool_itemsize: int) -> int:
    """VMEM the kernel holds for one row: the score strip, the two page
    buffers, the softmax statistics, the accumulator and the pipelined q,
    output and mass blocks."""
    hpg = _heads_per_tile(hkv, dh)
    n_t, rows = hkv // hpg, hpg * hq // hkv * chunk   # tiles, their queries
    tile = n_t * rows * hpg * dh * 4
    bk = block_pages(max_len // page_size, page_size) * page_size
    strip = n_t * rows * max_len * 4
    bufs = 2 * bk * hkv * dh * pool_itemsize
    stats = 2 * n_t * rows * LANES * 4
    blocks = 2 * (2 * tile + hkv * max_len * 4)
    return strip + bufs + stats + tile + blocks


def fits(*, chunk: int, hq: int, hkv: int, dh: int, max_len: int,
         page_size: int, pool_itemsize: int) -> bool:
    """Whether the kernel serves these shapes: dh splits the 128-lane
    tiles evenly, a block is a whole lane tile of positions, and the
    kernel's VMEM fits its budget."""
    if hq % hkv or max_len % page_size:
        return False
    if (dh < LANES and LANES % dh) or (dh > LANES and dh % LANES):
        return False
    bk = block_pages(max_len // page_size, page_size) * page_size
    if bk % BLOCK_POSITIONS:
        return False
    return _vmem_bytes(chunk=chunk, hq=hq, hkv=hkv, dh=dh, max_len=max_len,
                       page_size=page_size,
                       pool_itemsize=pool_itemsize) <= VMEM_BUDGET


def _kernel(layer_ref, pt_ref, end_ref, qs_ref, ql_ref,      # scalar prefetch
            q_ref, k_hbm, v_hbm,                             # inputs
            o_ref, *rest,
            scale: float, chunk: int, n_rep: int, ps: int, n_pp: int,
            return_mass: bool):
    if return_mass:
        mass_ref, buf, sem, s_scr, m_scr, l_scr, acc_scr = rest
    else:
        mass_ref = None
        buf, sem, s_scr, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    li = layer_ref[0]
    kv_end = end_ref[b]
    q_start = qs_ref[b]
    q_len = ql_ref[b]
    _, n_t, rows, wg = q_ref.shape          # rows = hpg * n_rep * chunk
    bk = buf.shape[1]                       # positions a block covers
    bp = bk // ps
    nb = jnp.where(q_len > 0, pl.cdiv(kv_end, bk), 0)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def copies(i, slot, pool):
        """The page DMAs of load ``i`` (K block i, then V block i - nb)."""
        blk = jnp.where(i < nb, i, i - nb)
        base = b * n_pp + blk * bp
        return [pltpu.make_async_copy(
            pool.at[li, pt_ref[base + j]],
            buf.at[slot, pl.ds(j * ps, ps)], sem.at[slot])
            for j in range(bp)]

    def start(i, slot):
        @pl.when(i < nb)
        def _():
            for c in copies(i, slot, k_hbm):
                c.start()

        @pl.when(i >= nb)
        def _():
            for c in copies(i, slot, v_hbm):
                c.start()

    # query row r of a tile is (head in tile, repeat, chunk position c)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 0)
    row_c = row if rows == chunk else row % chunk

    def body(i, carry):
        slot = i % 2

        @pl.when(i + 1 < 2 * nb)
        def _():
            start(i + 1, 1 - slot)

        for c in copies(i, slot, k_hbm):
            c.wait()

        @pl.when(i < nb)
        def _scores():
            off = pl.multiple_of(i * bk, bk)
            k_pos = off + jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1)
            visible = ((k_pos <= q_start + row_c) & (k_pos < kv_end)
                       & (row_c < q_len))
            for t in range(n_t):
                k = buf[slot, :, pl.ds(t * wg, wg)].astype(jnp.float32)
                s = jax.lax.dot_general(
                    q_ref[0, t], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                s = jnp.where(visible, s, NEG_INF)
                s_scr[t, :, pl.ds(off, bk)] = s
                m_scr[t] = jnp.maximum(m_scr[t],
                                       jnp.max(s, axis=1, keepdims=True))

        @pl.when(i >= nb)
        def _values():
            off = pl.multiple_of((i - nb) * bk, bk)
            for t in range(n_t):
                v = buf[slot, :, pl.ds(t * wg, wg)].astype(jnp.float32)
                s = s_scr[t, :, pl.ds(off, bk)]
                # a fully masked query keeps m at NEG_INF: exact zeros,
                # not exp(0)
                pr = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_scr[t]))
                l_scr[t] = l_scr[t] + jnp.sum(pr, axis=1, keepdims=True)
                if return_mass:
                    s_scr[t, :, pl.ds(off, bk)] = pr
                acc_scr[t] = acc_scr[t] + jax.lax.dot_general(
                    pr, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
        return carry

    @pl.when(nb > 0)
    def _run():
        start(0, 0)
        jax.lax.fori_loop(0, 2 * nb, body, 0)

    for t in range(n_t):
        o_ref[0, t] = acc_scr[t] / jnp.maximum(l_scr[t], 1e-30)

    if return_mass:
        # kv head t * hpg + p: the mean over its n_rep q heads of their
        # normalised probabilities, summed over the row's valid queries
        mass_ref[0] = jnp.zeros(mass_ref.shape[1:], mass_ref.dtype)
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        q_ok = (r if rows == chunk else r % chunk) < q_len
        hpg = mass_ref.shape[1] // n_t      # kv heads a lane tile holds
        per_head = rows // hpg
        heads = [(r >= p * per_head) & (r < (p + 1) * per_head) & q_ok
                 for p in range(hpg)]

        def mass_block(blk, carry):
            off = pl.multiple_of(blk * bk, bk)
            for t in range(n_t):
                inv = 1.0 / (n_rep * jnp.maximum(l_scr[t], 1e-30))
                pr = s_scr[t, :, pl.ds(off, bk)]
                for p in range(hpg):
                    mass_ref[0, pl.ds(t * hpg + p, 1), pl.ds(off, bk)] = (
                        jnp.sum(pr * jnp.where(heads[p], inv, 0.0), axis=0,
                                keepdims=True))
            return carry

        jax.lax.fori_loop(0, nb, mass_block, 0)


@functools.partial(jax.jit, static_argnames=("scale", "return_mass",
                                             "interpret"))
def paged_decode_attention(q, k_pool, v_pool, layer, page_table, kv_end,
                           q_start, q_lens, *, scale: float,
                           return_mass: bool = False,
                           interpret: bool = False):
    """Attention of each row's query chunk against its own pages.

    q: (ns, C, hq, dh) queries, in the space K is stored in;
    k_pool, v_pool: (L, P, page_size, hkv * dh) the whole stacked pools,
    read in place; layer: () int32, the layer to read;
    page_table: (ns, pages_per_slot) int32;
    kv_end: (ns,) keys visible to the row after this step's write (0 for
    a dead lane); q_start: (ns,) cache position of each row's first
    query; q_lens: (ns,) valid queries per row (0 for a dead lane).

    Returns o (ns, C, hq, dh) float32, and with ``return_mass`` the
    per-key mass (ns, hkv, M) float32 (see the module docstring)."""
    ns, C, hq, dh = q.shape
    _, _, ps, width = k_pool.shape
    hkv = width // dh
    n_rep = hq // hkv
    n_pp = page_table.shape[1]
    M = n_pp * ps
    hpg = _heads_per_tile(hkv, dh)
    n_t, wg = hkv // hpg, hpg * dh
    rows = n_rep * C
    bp = block_pages(n_pp, ps)

    # (ns, n_t, hpg * rows, wg): lane tile t holds kv heads t * hpg + p,
    # p < hpg, in lanes [p * dh, (p + 1) * dh); its query rows are
    # (p, repeat j, chunk position c), each zero outside its head's lanes
    qg = q.astype(jnp.float32).reshape(ns, C, n_t, hpg, n_rep, dh)
    qg = qg.transpose(0, 2, 3, 4, 1, 5).reshape(ns, n_t, hpg, rows, 1, dh)
    own = jnp.eye(hpg, dtype=jnp.float32).reshape(1, 1, hpg, 1, hpg, 1)
    qg = (qg * own).reshape(ns, n_t, hpg * rows, wg)

    kernel = functools.partial(
        _kernel, scale=scale, chunk=C, n_rep=n_rep, ps=ps, n_pp=n_pp,
        return_mass=return_mass)
    tile = (1, n_t, hpg * rows, wg)
    out_shape = [jax.ShapeDtypeStruct((ns,) + tile[1:], jnp.float32)]
    out_specs = [pl.BlockSpec(tile, lambda b, *_: (b, 0, 0, 0))]
    if return_mass:
        out_shape.append(jax.ShapeDtypeStruct((ns, hkv, M), jnp.float32))
        out_specs.append(pl.BlockSpec((1, hkv, M), lambda b, *_: (b, 0, 0)))
    res = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(ns,),
            in_specs=[
                pl.BlockSpec(tile, lambda b, *_: (b, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((2, bp * ps, width), k_pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((n_t, hpg * rows, M), jnp.float32),
                pltpu.VMEM((n_t, hpg * rows, 1), jnp.float32),
                pltpu.VMEM((n_t, hpg * rows, 1), jnp.float32),
                pltpu.VMEM((n_t, hpg * rows, wg), jnp.float32),
            ]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      page_table.reshape(-1).astype(jnp.int32),
      kv_end.astype(jnp.int32), q_start.astype(jnp.int32),
      q_lens.astype(jnp.int32), qg, k_pool, v_pool)
    # each query row's own head's lanes: the diagonal of (row head, lane
    # head)
    o = res[0].reshape(ns, n_t, hpg, n_rep, C, hpg, dh)
    o = jnp.stack([o[:, :, p, :, :, p] for p in range(hpg)], axis=2)
    o = o.transpose(0, 4, 1, 2, 3, 5).reshape(ns, C, hq, dh)
    return (o, res[1]) if return_mass else o
