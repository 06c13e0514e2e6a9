"""Chip benchmark of the serving engine.

One run serves one cell of ``BENCHMARK.json`` (a model configuration under
one traffic mix) on the chip through ``repro.serve.frontend.FrontEnd``,
measures for a fixed window, checks the served tokens against a plain
float32 reference and prints one JSON line. Everything that belongs to one
configuration, mix or per-layer metric is a file of its own, found by name:

    chipbench/configs/<config>.json   sizes, engine settings, check limit
    chipbench/mixes/<traffic>.json    traffic parameters (one generator)
    chipbench/metrics/<metric>.py     one per-layer reader each

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>
"""
