"""A run whose timed path is broken underneath must come out not correct:
a token altered where it is produced, a step that leaves the cache as it
was, and a rank decision that keeps the wrong eigenvectors."""
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from chipbench import run

DATA = Path(__file__).parent / "data"


def altered_token(eng):
    core = eng.core
    orig = core._select_token

    def select(logits, *a):
        return (orig(logits, *a) + 1) % logits.shape[-1]
    core._select_token = select


def state_unchanged(eng):
    core = eng.core
    for name in ("_step_impl", "_step_mixed_impl"):
        orig = getattr(core, name)

        def stale(*a, orig=orig):
            pools, tok, ob, lens = orig(*a)
            keep = {"k": a[1], "v": a[2], "kt": a[3], "mass": a[4]}
            return {k: keep[k] for k in pools}, tok, ob, lens
        setattr(core, name.replace("_impl", ""), jax.jit(stale))


def weakest_basis(eng):
    core = eng.core
    orig = core._decide

    def decide(*a):
        ranks, basis, spectra, kt, veto = orig(*a)
        return ranks, jnp.flip(basis, -1), spectra, kt, veto
    core._decide = decide


@pytest.mark.parametrize("fault", [altered_token, state_unchanged,
                                   weakest_basis])
def test_fault_is_not_correct(fault):
    res = run.run_cell("tiny-gqa.chat", 77, 2.0, False, root=DATA,
                       require_tpu=False, fault=fault)
    assert res["correct"] is False
    chk = res["checks"]["logit_gap"]
    assert chk["value"] > chk["limit"]
