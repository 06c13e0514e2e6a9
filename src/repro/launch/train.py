"""Training launcher.

CPU example:    PYTHONPATH=src python -m repro.launch.train --arch drrl-paper \
                    --reduced --steps 50
Production dry: the mesh/sharding path used here is exactly what
                repro.launch.dryrun lowers for the 256/512-chip meshes.
"""
from __future__ import annotations

import argparse

import jax

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config
from repro.configs.base import TrainConfig
from repro.data.synthetic import SyntheticLM
from repro.dist import sharding as shd
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.api import get_model
from repro.train.loop import run_training


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="drrl-paper")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", default="none")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    fns = get_model(cfg)
    tc = TrainConfig(global_batch=args.batch, seq_len=args.seq, lr=args.lr,
                     total_steps=args.steps, warmup_steps=max(args.steps // 10, 1),
                     microbatches=args.microbatches,
                     grad_compression=args.grad_compression,
                     checkpoint_every=max(args.steps // 2, 1),
                     checkpoint_dir=args.ckpt_dir or f"/tmp/repro_{args.arch}")
    data = SyntheticLM(cfg.vocab_size, tc.seq_len, tc.global_batch, tc.seed)
    mesh = make_host_mesh()
    ckpt = CheckpointManager(tc.checkpoint_dir) if args.ckpt_dir else None

    kw = {}
    if cfg.rank.mode == "drrl":
        from repro.core.drrl import init_agent
        agent = init_agent(jax.random.PRNGKey(7), cfg.rank, cfg.d_model)
        kw = {"policy_params": agent}

    def loss_fn(p, b, rng):
        extra = {"rank_rng": rng, **kw} if cfg.rank.mode == "drrl" else {}
        return fns.loss(p, b, **extra)

    with mesh:
        params_shape = jax.eval_shape(fns.init, jax.random.PRNGKey(tc.seed))
        pspecs = shd.param_pspecs(params_shape, cfg, mesh)
        out = run_training(cfg, tc, init_fn=fns.init, loss_fn=loss_fn,
                           data=data, ckpt_manager=ckpt, param_specs=pspecs)
    print(f"final loss: {out['history'][-1]['loss']:.4f}")
    return out


if __name__ == "__main__":
    main()
