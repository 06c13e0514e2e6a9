"""Small serving CLI over ``repro.serve.api``.

The serving surface lives in ``repro.serve.api``: ``EngineConfig`` +
``SamplingParams`` + ``Engine.submit(prompt, params) -> RequestHandle``
with chunked prefill interleaved into the fused decode step.

    PYTHONPATH=src python -m repro.launch.serve --reduced --tokens 8
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.models.api import get_model
from repro.serve.api import Engine, EngineConfig, SamplingParams

__all__ = ["main"]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="drrl-paper")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=64)
    ap.add_argument("--chunk", type=int, default=16,
                    help="prompt tokens consumed per fused step")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    fns = get_model(cfg)
    params = fns.init(jax.random.PRNGKey(0))
    policy = None
    if cfg.rank.mode == "drrl":
        from repro.core.drrl import init_agent
        policy = init_agent(jax.random.PRNGKey(7), cfg.rank, cfg.d_model)
    eng = Engine(cfg, params, policy, config=EngineConfig(
        n_slots=args.batch, max_len=args.prompt_len + args.tokens + 8,
        segment_len=16, max_new_cap=args.tokens,
        prefill_chunk=args.chunk,
        sampling=False))      # greedy-only CLI: keep the lean step
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    import numpy as np
    handles = [eng.submit(np.asarray(prompts[i]),
                          SamplingParams(max_new=args.tokens))
               for i in range(args.batch)]
    eng.warmup()
    eng.run()
    s = eng.stats
    tps = s["tokens_decoded"] / max(s["decode_s"], 1e-9)
    ranks = eng.core.ranks_per_step()
    print(f"decoded ({args.batch}, {args.tokens}) at {tps:.1f} tok/s "
          f"(compile {s['compile_s']:.2f}s, "
          f"mixed steps {s['mixed_steps']}); "
          f"per-slot rank schedule: {[r.tolist() for r in ranks[:8]]}...")
    print(f"TTFT per request: "
          f"{['%.3fs' % h.ttft_s for h in handles if h.ttft_s is not None]}")


if __name__ == "__main__":
    main()
