"""Compile-only checks for a TPU v5e that is described, not attached.

The chip's compiler must accept every form of the Pallas flash-decode
kernel the serving engine dispatches, at the drrl-paper widths (12 q and
12 kv heads of 64, 8 rows), and lower each to a Mosaic kernel
(``tpu_custom_call``) rather than to interpreted XLA. The fused paged
step must keep its K/V pools in place: at the drrl-paper deployment no
copy or slice of a whole pool, and little scratch memory.

Nothing runs: these catch what interpret mode cannot (tiling, fast-memory
budget) at no chip time. The topology is described inside a fixture, so
collection never loads the TPU library; without it the tests skip."""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attn import flash_decode
from repro.models.api import get_model
from repro.serve import PagedKVCache

HQ = HKV = 12
DH = 64
ROWS = 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    # an entry compiled for a described chip cannot be read back here
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # no TPU compiler in this installation
                pytest.skip(f"no v5e:2x2 topology can be described: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(sharding, max_len, chunk, probs):
    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    q = spec((ROWS, HQ, DH) if chunk is None else (ROWS, HQ, chunk, DH))
    kv = spec((ROWS, HKV, max_len, DH))
    rows = spec((ROWS,), jnp.int32)
    if chunk is None:
        fn = lambda q, k, v, n: flash_decode(q, k, v, n, scale=DH ** -0.5,
                                             return_probs=probs)
        args = (q, kv, kv, rows)
    else:
        fn = lambda q, k, v, n, s: flash_decode(
            q, k, v, n, scale=DH ** -0.5, return_probs=probs, q_start=s)
        args = (q, kv, kv, rows, rows)
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("max_len,chunk,probs", [
    (2048, None, False),    # single decode token
    (2048, None, True),     # single token + attention-mass rows
    (2048, 16, True),       # chunked prefill / spec verify, with q_start
    (32768, 16, True),      # the same at a long context
])
def test_flash_decode_compiles_for_v5e(one_chip, max_len, chunk, probs):
    assert "tpu_custom_call" in _compiled_text(one_chip, max_len, chunk,
                                               probs)


def _mixed_step_compiled(sharding, n_slots=32, max_len=2048, chunk=16):
    """The mixed fused step (chunked prefill beside decode rows) of the
    drrl-paper deployment, float32 pools donated as the engine donates
    them on a chip."""
    cfg = get_config("drrl-paper")
    fns = get_model(cfg)

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    params = jax.tree_util.tree_map(
        spec, jax.eval_shape(fns.init, jax.random.PRNGKey(0)))
    # the cache's own storage, traced abstractly (nothing is allocated)
    cache = jax.eval_shape(lambda: {
        k: getattr(c, k) for c in [PagedKVCache(cfg, n_slots, max_len)]
        for k in ("k_pool", "v_pool", "mass_pool", "basis", "page_table")})
    rows = spec(jax.ShapeDtypeStruct((n_slots,), jnp.int32))
    flags = spec(jax.ShapeDtypeStruct((n_slots,), bool))
    args = (params, spec(cache["k_pool"]), spec(cache["v_pool"]),
            spec(cache["mass_pool"]), spec(cache["page_table"]),
            spec(jax.ShapeDtypeStruct((n_slots, chunk), jnp.int32)),
            rows, rows, spec(cache["basis"]), flags, rows, flags)

    def step(params, pk, pv, mass, pt, toks, lens, ranks, basis, active,
             q_lens, prefill_rows):
        return fns.decode_step_paged(
            params, pk, pv, pt, toks, slot_lens=lens, slot_ranks=ranks,
            basis=basis, active=active, mass_pool=mass, q_lens=q_lens,
            prefill_rows=prefill_rows)

    compiled = jax.jit(step, donate_argnums=(1, 2, 3)).lower(*args).compile()
    return compiled, cache["k_pool"].shape[1]


def test_mixed_step_keeps_pools_in_place_for_v5e(one_chip):
    compiled, n_pages = _mixed_step_compiled(one_chip)
    moved = []
    op = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) "
                    r"(copy|dynamic-slice|dynamic-update-slice)\(")
    for line in compiled.as_text().splitlines():
        m = op.match(line)
        if m and re.search(rf"[\[,]{n_pages}[,\]]", m.group(2)):
            moved.append(m.group(1, 3, 2))
    assert not moved, f"whole-pool copies or slices: {moved[:8]}"
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1.5e9, f"{temp / 1e9:.2f} GB of temporaries"
