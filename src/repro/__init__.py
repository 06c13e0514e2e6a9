"""repro: paper reproduction framework (models, kernels, dist, launch)."""
