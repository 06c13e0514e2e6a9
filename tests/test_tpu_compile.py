"""Compile-only checks of the Pallas flash-decode kernel for a TPU v5e
that is described, not attached: the chip's compiler must accept every
form the serving engine dispatches, at the drrl-paper widths (12 q and
12 kv heads of 64, 8 rows), and lower each to a Mosaic kernel
(``tpu_custom_call``) rather than to interpreted XLA.

Nothing runs: these catch what interpret mode cannot (tiling, fast-memory
budget) at no chip time. The topology is described inside a fixture, so
collection never loads the TPU library; without it the tests skip."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attn import flash_decode

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
