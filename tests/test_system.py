"""End-to-end behaviour tests for the DR-RL system: training converges,
checkpoint/restart resumes bit-exact, adaptive serving dispatches rank
buckets, and the DR-RL modes trade fidelity for FLOPs as the paper claims."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config
from repro.configs.base import RankConfig, TrainConfig
from repro.core.rewards import flops_fraction
from repro.data.synthetic import SyntheticLM
from repro.models import transformer as tr
from repro.models.api import get_model
from repro.optim import adamw
from repro.train.loop import make_train_step, run_training

RNG = jax.random.PRNGKey(0)


def test_training_reduces_loss():
    cfg = get_config("drrl-paper", reduced=True).with_(
        rank=RankConfig(mode="off"))
    fns = get_model(cfg)
    tc = TrainConfig(global_batch=4, seq_len=64, lr=1e-3, total_steps=30,
                     warmup_steps=3, checkpoint_every=0, log_every=100)
    data = SyntheticLM(cfg.vocab_size, tc.seq_len, tc.global_batch, seed=0)
    out = run_training(cfg, tc, init_fn=fns.init,
                       loss_fn=lambda p, b, r: fns.loss(p, b), data=data)
    h = out["history"]
    assert h[-1]["loss"] < h[0]["loss"] - 0.3, h


def test_checkpoint_restart_is_bit_exact(tmp_path):
    cfg = get_config("drrl-paper", reduced=True).with_(
        rank=RankConfig(mode="off"))
    fns = get_model(cfg)
    data = SyntheticLM(cfg.vocab_size, 32, 2, seed=0)
    tc = TrainConfig(global_batch=2, seq_len=32, lr=1e-3, total_steps=6,
                     warmup_steps=1, checkpoint_every=3, log_every=100,
                     async_checkpoint=False, schedule="constant")

    # run A: 6 steps straight through
    outA = run_training(cfg, tc, init_fn=fns.init,
                        loss_fn=lambda p, b, r: fns.loss(p, b), data=data)
    # run B: 3 steps with checkpoint, then "crash" and resume
    cmB = CheckpointManager(str(tmp_path), async_save=False)
    tcB = dataclasses.replace(tc, total_steps=3)
    run_training(cfg, tcB, init_fn=fns.init,
                 loss_fn=lambda p, b, r: fns.loss(p, b), data=data,
                 ckpt_manager=cmB)
    outB = run_training(cfg, tc, init_fn=fns.init,
                        loss_fn=lambda p, b, r: fns.loss(p, b), data=data,
                        ckpt_manager=cmB)
    for a, b in zip(jax.tree_util.tree_leaves(outA["params"]),
                    jax.tree_util.tree_leaves(outB["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6)


def test_drrl_flops_reduction_vs_fidelity():
    """Paper core claim at unit scale: rank truncation cuts score FLOPs while
    keeping attention-output fidelity high."""
    base = get_config("drrl-paper", reduced=True)
    cfg = base.with_(rank=RankConfig(mode="adaptive", rank_grid=(4, 8, 12, 16),
                                     energy_threshold=0.90))
    params = tr.init_dense(cfg, RNG)
    toks = jax.random.randint(RNG, (2, 64), 0, cfg.vocab_size)
    _, aux = tr.forward_dense(cfg, params, toks, compute_fidelity=True,
                              collect_aux="ranks", rank_rng=RNG)
    la = aux["layers"]
    fid = float(np.mean(np.asarray(la["fidelity"])))
    ranks = np.asarray(la["rank"]).astype(np.float32)
    frac = float(np.mean(np.asarray(
        flops_fraction(jnp.asarray(ranks), 16, 16))))
    assert fid > 0.9, fid
    assert frac < 0.95, frac


def test_adaptive_server_rank_dispatch():
    """Two streams through one engine: each step records a rank per
    stream from the grid, and each stream decodes as a 1-slot engine
    serving it alone would."""
    from repro.serve import Engine, EngineConfig, SamplingParams
    cfg = get_config("drrl-paper", reduced=True).with_(
        rank=RankConfig(mode="adaptive", rank_grid=(4, 8, 12, 16),
                        segment_len=8))
    fns = get_model(cfg)
    params = fns.init(RNG)
    prompts = np.asarray(jax.random.randint(RNG, (2, 16), 0,
                                            cfg.vocab_size))
    eng = Engine(cfg, params, config=EngineConfig(
        n_slots=2, max_len=96, segment_len=8, max_new_cap=24))
    hs = [eng.submit(p, SamplingParams(max_new=24)) for p in prompts]
    eng.warmup()
    eng.run()
    tokens = np.stack([h.result() for h in hs])
    assert tokens.shape == (2, 24)
    # per-step per-stream rank record: one prefill step (the 16-token
    # prompts fit the default chunk), then 23 decode steps
    ranks = [r.tolist() for r in eng.core.ranks_per_step()]
    assert len(ranks) == 24
    used = {r for step in ranks for r in step}
    assert used <= set(cfg.rank.rank_grid) | {-1}
    assert eng.stats["compile_s"] > 0.0 and eng.stats["decode_s"] > 0.0
    solo = Engine(cfg, params, config=EngineConfig(
        n_slots=1, max_len=96, segment_len=8, max_new_cap=24))
    for i, p in enumerate(prompts):
        solo.reset()
        h = solo.submit(p, SamplingParams(max_new=24))
        solo.run()
        np.testing.assert_array_equal(tokens[i], h.result())


def test_grad_accumulation_matches_single_batch():
    cfg = get_config("drrl-paper", reduced=True).with_(
        rank=RankConfig(mode="off"))
    fns = get_model(cfg)
    data = SyntheticLM(cfg.vocab_size, 32, 4, seed=0)
    batch = data.batch_at(0)
    params = fns.init(RNG)
    opt = adamw.init(params)
    tc1 = TrainConfig(global_batch=4, seq_len=32, microbatches=1,
                      lr=1e-3, warmup_steps=1)
    tc2 = TrainConfig(global_batch=4, seq_len=32, microbatches=2,
                      lr=1e-3, warmup_steps=1)
    s1 = jax.jit(make_train_step(cfg, tc1, lambda p, b, r: fns.loss(p, b)))
    s2 = jax.jit(make_train_step(cfg, tc2, lambda p, b, r: fns.loss(p, b)))
    p1, _, m1 = s1(params, opt, batch, RNG)
    p2, _, m2 = s2(params, opt, batch, RNG)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-4)


def test_bf16_grad_compression_close_to_fp32():
    cfg = get_config("drrl-paper", reduced=True).with_(
        rank=RankConfig(mode="off"))
    fns = get_model(cfg)
    data = SyntheticLM(cfg.vocab_size, 32, 4, seed=0)
    batch = data.batch_at(0)
    params = fns.init(RNG)
    opt = adamw.init(params)
    tc = TrainConfig(global_batch=4, seq_len=32, microbatches=2, lr=1e-3,
                     warmup_steps=1)
    s_fp = jax.jit(make_train_step(cfg, tc, lambda p, b, r: fns.loss(p, b)))
    s_bf = jax.jit(make_train_step(cfg, tc, lambda p, b, r: fns.loss(p, b),
                                   grad_compression="bf16"))
    p1, _, _ = s_fp(params, opt, batch, RNG)
    p2, _, _ = s_bf(params, opt, batch, RNG)
    deltas = [float(jnp.max(jnp.abs(a - b)))
              for a, b in zip(jax.tree_util.tree_leaves(p1),
                              jax.tree_util.tree_leaves(p2))]
    assert max(deltas) < 5e-3, max(deltas)
