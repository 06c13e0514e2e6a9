"""Compile-only checks for a TPU v5e that is described, not attached.

The chip's compiler must accept every form of the Pallas flash-decode
kernel the serving engine dispatches, at the drrl-paper widths (12 q and
12 kv heads of 64, 8 rows), and lower each to a Mosaic kernel
(``tpu_custom_call``) rather than to interpreted XLA. The fused paged
step must keep its K/V pools in place: at the drrl-paper deployment no
copy or slice of a whole pool, and little scratch memory.

The engine's fused steps and its segment decision must fit one chip at
the InternLM2-20B long-context deployment (4 of its 48 layers at
published widths, bfloat16, 8 slots of 12288 positions, the factor pool
on) with room to spare, and its fused steps must read each of the 8 kv
heads once for its 6 q heads instead of repeating K and V to 48 heads.

At the drrl-paper chat deployment (32 slots of 2048 positions, dense K)
both fused steps attend with the paged Pallas kernel, which reads the
carried pools in place: no gathered or re-tiled K/V and no whole-pool
copy. The longctx steps, which read K in factor form, keep their XLA
attention.

Nothing runs: these catch what interpret mode cannot (tiling, fast-memory
budget) at no chip time. The topology is described inside a fixture, so
collection never loads the TPU library; without it the tests skip."""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import RankConfig
from repro.core.drrl import init_agent
from repro.kernels import ops
from repro.kernels.decode_attn import flash_decode
from repro.models import transformer
from repro.models.api import get_model
from repro.models.attention import attend
from repro.serve import EngineConfig, PagedKVCache, ServeEngine

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


def _pool_moves(text, n_pages):
    """Copies and (dynamic) slices of a whole page pool."""
    moved = []
    op = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) "
                    r"(copy|dynamic-slice|dynamic-update-slice)\(")
    for line in text.splitlines():
        m = op.match(line)
        if m and re.search(rf"[\[,]{n_pages}[,\]]", m.group(2)):
            moved.append(m.group(1, 3, 2))
    return moved


def test_mixed_step_keeps_pools_in_place_for_v5e(one_chip):
    compiled, n_pages = _mixed_step_compiled(one_chip)
    moved = _pool_moves(compiled.as_text(), n_pages)
    assert not moved, f"whole-pool copies or slices: {moved[:8]}"
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1.5e9, f"{temp / 1e9:.2f} GB of temporaries"


# One v5e holds 16 GB. The longctx steps compile to 7.48 GB of
# arguments (weights 5.4, K/V pools 1.6, factor pool 0.45) and 1.31 /
# 1.72 GB of temporaries (pure decode / mixed), ``decide`` to 1.28 +
# 0.43 GB. The limits leave the rest of the chip for the eager programs
# and fail a change that brings back pool-sized temporaries (a K or V
# pool is 0.8 GB; when the layer scan sliced and restacked the pools
# each step, the steps took 5.8-5.9 GB of them).
STEP_LIMIT = 12e9
DECIDE_TEMP_LIMIT = 1e9


def _engine_programs(sharding, cfg, *, n_slots, max_len, max_new,
                     decide=False):
    """The engine's two fused steps (and ``decide``) at a deployment,
    lowered on shapes only, donating what the engine donates on a chip.
    The engine is built at a tiny size: the executables take every pool
    and weight as arguments, so only the widths come from it. The fused
    steps choose their attention as on a chip: the platform check of
    ``paged_attention_selected`` answers TPU, and the Pallas kernels
    compile through Mosaic instead of the interpreter."""
    agent = init_agent(jax.random.PRNGKey(1), cfg.rank, cfg.d_model)
    eng = ServeEngine(cfg, None, agent, config=EngineConfig(
        n_slots=1, max_len=16, max_new_cap=max_new, prefill_chunk=16))

    def spec(x, dtype=None):
        if x is None:
            return None
        if isinstance(x, tuple):
            x = jax.ShapeDtypeStruct(x, dtype)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    params = jax.tree_util.tree_map(
        spec, jax.eval_shape(get_model(cfg).init, jax.random.PRNGKey(0)))
    c = jax.eval_shape(lambda: {
        k: getattr(pc, k)
        for pc in [PagedKVCache(cfg, n_slots, max_len)]
        for k in ("k_pool", "v_pool", "kt_pool", "mass_pool", "basis",
                  "spectra", "ranks")})
    c = {k: spec(v) for k, v in c.items()}
    rows = spec((n_slots,), jnp.int32)
    pt = spec((n_slots, max_len // 16), jnp.int32)
    args = [params, c["k_pool"], c["v_pool"], c["kt_pool"], c["mass_pool"],
            pt, spec((n_slots, 1), jnp.int32), rows, c["ranks"],
            c["basis"], spec((n_slots,), bool),
            spec((n_slots + 1, max_new), jnp.int32), rows,
            spec((n_slots,), jnp.float32), rows,
            spec((n_slots,), jnp.float32), spec((n_slots,), jnp.uint32)]
    donate = (1, 2, 3, 4, 11)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(transformer, "_on_tpu", lambda: True)
        mp.setattr(ops, "_on_cpu", lambda: False)
        out = {"step": jax.jit(eng._step_impl, donate_argnums=donate)
               .lower(*args).compile(),
               "mixed": jax.jit(eng._step_mixed_impl, donate_argnums=donate)
               .lower(*args, spec((n_slots, max_len), jnp.int32)).compile()}
    out["kv_pages"] = c["k_pool"].shape[1]
    if decide:
        scalar = spec((), jnp.int32)
        out["decide"] = jax.jit(eng._decide.__wrapped__,
                                donate_argnums=(2, 6, 7)).lower(
            c["k_pool"], c["mass_pool"], c["kt_pool"], pt, rows, c["ranks"],
            c["basis"], c["spectra"], scalar, spec((), bool),
            scalar).compile()
    return out


def _longctx_programs(sharding, n_slots=8, max_len=12288, max_new=4096):
    """The fused steps and ``decide`` at the longctx deployment."""
    grid = (16, 24, 32, 40, 48, 56, 64)
    cfg = get_config("internlm2-20b").with_(
        num_layers=4, max_seq_len=32768,
        rank=RankConfig(mode="drrl", rank_grid=grid, segment_len=512))
    assert PagedKVCache(cfg, 1, 16).factored    # r_keep 64 < head_dim 128
    return _engine_programs(sharding, cfg, n_slots=n_slots, max_len=max_len,
                            max_new=max_new, decide=True)


@pytest.fixture(scope="module")
def longctx_programs(one_chip):
    return _longctx_programs(one_chip)


def test_longctx_steps_and_decide_fit_one_v5e(longctx_programs):
    progs = longctx_programs
    for name in ("step", "mixed"):
        m = progs[name].memory_analysis()
        total = m.argument_size_in_bytes + m.temp_size_in_bytes
        assert total < STEP_LIMIT, (
            f"{name}: {m.argument_size_in_bytes / 1e9:.2f} GB of arguments"
            f" + {m.temp_size_in_bytes / 1e9:.2f} GB of temporaries")
    temp = progs["decide"].memory_analysis().temp_size_in_bytes
    assert temp < DECIDE_TEMP_LIMIT, f"decide: {temp / 1e9:.2f} GB"


# GQA 6:1 at the longctx deployment: 48 q heads read 8 kv heads of 128
# (64 factor columns). Before the grouped contraction the steps repeated
# V and the K factors to 48 heads, [8,12288,8,6,128] and [8,12288,8,6,64],
# each layer of each step, and the pure-decode step took 3.03 GB of
# temporaries.
LONGCTX_HQ, LONGCTX_HKV, LONGCTX_LEN = 48, 8, 12288
LONGCTX_WIDTHS = (64, 128)
STEP_TEMP_LIMIT = 2e9


def test_longctx_steps_read_each_kv_head_once(longctx_programs):
    """No tensor of either fused step spans the cache positions, a head
    width and the q heads, whether as 48 heads or as the (8, 6) pair of
    kv head and repeat: K and V are contracted per kv group, never
    repeated to the q heads."""
    group = (LONGCTX_HKV, LONGCTX_HQ // LONGCTX_HKV)
    for name in ("step", "mixed"):
        repeated = set()
        for m in re.finditer(r"\[([\d,]+)\]",
                             longctx_programs[name].as_text()):
            dims = [int(x) for x in m.group(1).split(",")]
            if (LONGCTX_LEN in dims
                    and any(w in dims for w in LONGCTX_WIDTHS)
                    and (LONGCTX_HQ in dims
                         or group in zip(dims, dims[1:]))):
                repeated.add(m.group(0))
        assert not repeated, f"{name}: K/V repeated to q heads: {repeated}"
    temp = longctx_programs["step"].memory_analysis().temp_size_in_bytes
    assert temp < STEP_TEMP_LIMIT, f"step: {temp / 1e9:.2f} GB of temporaries"


def _mha_attend(q, k, v, kv_len, scale):
    """``attend``'s MHA contraction before it learned GQA: the same masks
    and softmax chain over (b, h, sq, skv) scores, one kv head per q head."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    valid = jnp.arange(k.shape[1])[None, None, None, :] < kv_len
    s = jnp.where(valid, s, jnp.asarray(-1e30, jnp.float32))
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v), p


def _hlo_ops(compiled):
    """The compiled program's instructions, names and metadata dropped."""
    ops = []
    for line in compiled.as_text().splitlines():
        if (" = " not in line or "file_name_id" in line
                or line.startswith("HloModule")):
            continue
        line = re.sub(r",? ?(metadata|stack_frame_id|origin|backend_config|"
                      r"frontend_attributes)=(\{[^}]*\}|\S+)", "", line)
        ops.append(re.sub(r"%[\w.\-]+", "%", line).strip())
    return sorted(ops)


@pytest.mark.parametrize("sq", [1, 16])
def test_grouped_attend_compiles_as_mha_at_one_kv_head_per_q_head(one_chip,
                                                                   sq):
    """With as many kv heads as q heads (drrl-paper: 12 of 64, 32 rows of
    2048 positions, one decode token or a 16-token prefill chunk) the
    grouped contraction's size-1 group axis leaves no trace: ``attend``
    compiles to the same program as the plain MHA contraction."""
    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    n, skv = 32, 2048
    args = (spec((n, sq, HQ, DH)), spec((n, skv, HKV, DH)),
            spec((n, skv, HKV, DH)), spec((n, 1, sq, 1), jnp.int32))

    def grouped(q, k, v, kv_len):
        return attend(q, k, v, scale=DH ** -0.5, causal=False, kv_len=kv_len,
                      return_probs=True)

    def mha(q, k, v, kv_len):
        return _mha_attend(q, k, v, kv_len, DH ** -0.5)

    got, want = (_hlo_ops(jax.jit(f).lower(*args).compile())
                 for f in (grouped, mha))
    assert got == want


# The chat deployment: drrl-paper (12 q and 12 kv heads of 64, float32)
# serving 32 slots of 2048 positions with 16-token prefill chunks; K is
# stored dense (r_keep 64 == head_dim). Before the paged kernel each
# layer of each step gathered every slot's pages, [4096,16,768], and
# re-tiled them to [32,2048,12,64] for K and V.
CHAT_SLOTS, CHAT_LEN, CHAT_SHAPES = 32, 2048, ("[32,2048,12,64]",
                                               "[4096,16,768]")


@pytest.fixture(scope="module")
def chat_programs(one_chip):
    grid = (16, 24, 32, 40, 48, 56, 64)
    cfg = get_config("drrl-paper").with_(
        rank=RankConfig(mode="drrl", rank_grid=grid, segment_len=512))
    return _engine_programs(one_chip, cfg, n_slots=CHAT_SLOTS,
                            max_len=CHAT_LEN, max_new=512)


@pytest.mark.parametrize("name", ["step", "mixed"])
def test_chat_steps_read_pages_in_place_for_v5e(chat_programs, name):
    """Both fused steps of the chat deployment attend with the paged
    kernel, which reads the carried pools in place: no gathered or
    re-tiled K/V, and no copy of a whole pool."""
    text = chat_programs[name].as_text()
    assert "paged_decode_attention" in text
    assert "tpu_custom_call" in text
    found = [s for s in CHAT_SHAPES if s in text]
    assert not found, f"{name}: gathered or re-tiled K/V {found}"
    moved = _pool_moves(text, chat_programs["kv_pages"])
    assert not moved, f"{name}: whole-pool copies or slices {moved[:8]}"


def test_longctx_steps_keep_the_factor_read(longctx_programs):
    """The factor-form K read (longctx: a factor pool beside dense K)
    keeps its XLA attention: the paged kernel is chosen only where K is
    stored dense."""
    for name in ("step", "mixed"):
        assert "paged_decode_attention" not in longctx_programs[name].as_text()
