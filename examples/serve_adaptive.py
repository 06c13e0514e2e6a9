"""Batched adaptive serving: the DR-RL policy re-picks each stream's rank
bucket every segment (paper section 4.5.2), the perturbation guardrail
vetoes unsafe switches per slot, and heterogeneous ranks share ONE fused
decode executable (factor padding + rank masking — see repro.serve).

    PYTHONPATH=src python examples/serve_adaptive.py --tokens 96
"""
import argparse

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import RankConfig
from repro.core.drrl import init_agent
from repro.data.synthetic import SyntheticLM
from repro.models.api import get_model
from repro.serve import Engine, EngineConfig, SamplingParams
from repro.train.rl import train_agent


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=96)
    ap.add_argument("--segment", type=int, default=16)
    ap.add_argument("--mode", default="drrl",
                    choices=["drrl", "adaptive", "fixed", "off"])
    args = ap.parse_args()

    cfg = get_config("drrl-paper", reduced=True)
    cfg = cfg.with_(rank=RankConfig(mode=args.mode, rank_grid=(4, 8, 12, 16),
                                    fixed_rank=8, segment_len=args.segment))
    fns = get_model(cfg)
    params = fns.init(jax.random.PRNGKey(0))

    policy = None
    if args.mode == "drrl":
        policy = init_agent(jax.random.PRNGKey(7), cfg.rank, cfg.d_model)
        data = SyntheticLM(cfg.vocab_size, 48, 2, seed=3)
        print("warm-starting policy (BC + PPO)...")
        policy, _ = train_agent(cfg, params, policy, data, bc_steps=4,
                                ppo_steps=4, ppo_epochs=1)

    eng = Engine(cfg, params, policy, config=EngineConfig(
        n_slots=args.batch, max_len=args.prompt_len + args.tokens + 8,
        segment_len=args.segment, max_new_cap=args.tokens))
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0,
        cfg.vocab_size))
    handles = [eng.submit(p, SamplingParams(max_new=args.tokens))
               for p in prompts]
    eng.warmup()
    eng.run()
    tokens = np.stack([h.result() for h in handles])
    s = eng.stats
    ranks = [r.tolist() for r in eng.core.ranks_per_step()]
    print(f"decoded {tokens.shape[1]} tokens x {args.batch} streams "
          f"at {s['tokens_decoded'] / max(s['decode_s'], 1e-9):.1f} tok/s "
          f"(compile {s['compile_s']:.2f}s)")
    print(f"rank schedule (per step, per stream): {ranks}")
    buckets = sorted({r for step in ranks for r in step if r >= 0})
    print(f"rank buckets exercised: {buckets} (one fused executable)")


if __name__ == "__main__":
    main()
