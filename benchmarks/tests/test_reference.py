"""The plain reference against the program, at a toy width on the CPU
in float32 (on the chip the runners compare at the published widths).
Both compute the same function, so float32 must agree to rounding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference
from dmlc_tpu.models import transformer as tfm

CFG = tfm.TransformerConfig(vocab=512, d_model=64, n_heads=4, head_dim=16,
                            d_ff=128, n_layers=3, n_experts=1,
                            microbatches=1, dtype="float32")


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(jax.random.PRNGKey(0), CFG)


def _ids(shape, seed=1):
    return np.random.default_rng(seed).integers(0, CFG.vocab, shape,
                                                dtype=np.int32)


@pytest.mark.parametrize("t", [64, 1024])  # one block, and two of 512
def test_mean_loss_is_the_programs_loss(params, t):
    ids, labels = _ids((2, t)), _ids((2, t), seed=2)
    want = float(tfm.unsharded_loss(params, ids, labels, CFG))
    got = float(reference.mean_loss(params, ids, labels))
    assert got == pytest.approx(want, abs=2e-5)


def test_logits_at_are_the_programs_prefill_logits(params):
    ids = _ids((1, 96))
    want, _, _ = tfm.forward_prefill(params, ids, CFG)
    padded = np.zeros(512, np.int32)
    padded[:96] = ids[0]
    at = np.array([0, 50, 95])
    got = reference.logits_at(params, padded, at)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want)[0, at],
                               atol=2e-5)


def test_float8_control_moves_the_loss_further_than_float32_rounding(
        params):
    ids, labels = _ids((2, 64)), _ids((2, 64), seed=2)
    exact = float(reference.mean_loss(params, ids, labels))
    low = float(reference.mean_loss(params, ids, labels,
                                    quantize=jnp.float8_e4m3fn))
    assert abs(low - exact) > 1e-4


def test_rounded_logits_pick_tokens_the_exact_ones_score_lower(params):
    """The serve control of rehearsals/precision_controls.py: the top
    tokens of a float8 forward, scored by the exact one."""
    ids = np.zeros(512, np.int32)
    ids[:96] = _ids((96,))
    at = np.arange(32, 96)
    exact = np.asarray(reference.logits_at(params, ids, at))
    low = np.asarray(reference.logits_at(params, ids, at,
                                         quantize=jnp.float8_e4m3fn))
    gaps = exact.max(-1) - exact[np.arange(len(at)), low.argmax(-1)]
    assert (gaps >= 0).all() and gaps.max() > 0
    same = np.asarray(reference.logits_at(params, ids, at, quantize=None))
    np.testing.assert_array_equal(same, exact)


def test_reference_refuses_what_it_does_not_cover():
    cfg = tfm.TransformerConfig(vocab=64, d_model=16, n_heads=2,
                                head_dim=8, d_ff=32, n_layers=1,
                                n_experts=2, microbatches=1)
    p = tfm.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="dense block only"):
        reference.mean_loss(p, _ids((1, 8)) % 64, _ids((1, 8)) % 64)
