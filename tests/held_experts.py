"""The held experts' trip loop (``models.transformer._moe_held_ffn``)
driven the same way for every family that runs it: under the tile rule,
under forced 8-row trips, with routing skewed onto the held experts so
that the pairs overflow the rule's tile, and with the layer's groups at
the last offset of a stack whose other groups hold junk."""

import numpy as np

import jax
import jax.numpy as jnp

from dmlc_tpu.models import transformer as tfm

STACKS = ("w_in", "w_gate", "w_out")


def matrices(cfg) -> int:
    return 2 if cfg.moe_act == "relu2" else 3


def trips(counts, cfg, n: int) -> int:
    """The trips a layer made, read from its counts (pairs per held
    expert, pairs routed, grouped products), checked against the rule:
    one per tile of the held pairs, at least one."""
    counts = np.asarray(counts)
    held = int(counts[:-2].sum())
    made, rest = divmod(int(counts[-1]), matrices(cfg))
    assert rest == 0
    assert made == -(-held // tfm.moe_tile(n, cfg)), (made, held)
    return made


def skewed(p, x, cfg):
    """``(p, x)`` with a router that scores the held experts near 1 and
    the others near 0 for every token (the tokens still differ), the
    correction bias zeroed: every token puts min(topk, held) pairs here,
    the most that can land."""
    held = slice(cfg.moe_held_start, cfg.moe_held_start + cfg.n_experts)
    direction = jnp.sign(x[0, 0])
    gate = np.full(p["gate"].shape, -1.0, np.float32)
    gate[:, held] = 1.0
    p = dict(p, gate=jnp.asarray(gate, p["gate"].dtype) * direction[:, None]
             .astype(p["gate"].dtype) * 4.0 / x.shape[-1] ** 0.5)
    if p.get("gate_bias") is not None:
        p["gate_bias"] = jnp.zeros_like(p["gate_bias"])
    noise = jax.random.normal(jax.random.PRNGKey(11), x.shape, x.dtype)
    x = jnp.abs(x[:, :1]) * direction + 0.1 * noise
    # unit rms, as a normed input is (a reference may norm it again)
    return p, x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True))


def at_last_group(p, cfg, layers: int = 3):
    """``(p, first_group)``: the layer's held experts placed last in a
    stack of ``layers`` layers' groups, the others filled with junk a
    hundred times the weights' scale (an empty group reads nothing)."""
    x_l = cfg.n_experts
    out = dict(p)
    for name in STACKS:
        if name not in p:
            continue
        junk = 100.0 * jax.random.normal(
            jax.random.PRNGKey(3), ((layers - 1) * x_l,) + p[name].shape[1:],
            p[name].dtype)
        out[name] = jnp.concatenate([junk, p[name]])
    return out, (layers - 1) * x_l


def run(x, p, cfg, monkeypatch=None, first_group=0, forced=False):
    """``_moe_held_ffn`` under the rule, or (``forced``) with a trip
    budget of one byte, which the rule floors at 8 rows."""
    if not forced:
        return tfm._moe_held_ffn(x, p, cfg, first_group=first_group)
    with monkeypatch.context() as m:
        m.setattr(tfm, "MOE_TRIP_BYTES", 1)
        assert tfm.moe_tile(x.shape[0] * x.shape[1], cfg) == 8
        return tfm._moe_held_ffn(x, p, cfg, first_group=first_group)


def same(got, want):
    """Two runs of the same pairs: only the order in which a token's
    picks are added (trip by trip, in float32) may differ."""
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=1e-4, rtol=1e-5)


def check_case(case, p, x, cfg, reference, monkeypatch, atol):
    """One case of the families' tile tests: ``rule`` (the rule's trips
    and forced 8-row trips against the family's reference), ``overflow``
    (skewed routing: more pairs than the rule's tile, more than one
    trip), ``last_group`` (the layer's groups last in a junk stack),
    ``sparse`` (skewed routing onto a layer whose expected pairs are
    sparse over its groups: more than 512 pairs in one trip).
    ``atol``: the distance from the reference, relative and absolute."""
    n = x.shape[0] * x.shape[1]
    if case in ("overflow", "sparse"):
        p, x = skewed(p, x, cfg)
    want = np.asarray(reference(x, p, cfg), np.float32)
    y, counts = run(x, p, cfg)
    np.testing.assert_allclose(np.asarray(y[0], np.float32), want,
                               atol=atol, rtol=atol)
    made = trips(counts, cfg, n)
    if case in ("rule", "sparse"):
        forced_y, forced_counts = run(x, p, cfg, monkeypatch, forced=True)
        same(forced_y, y)
        assert int(np.asarray(forced_counts)[-1]) == matrices(cfg) * -(
            -int(np.asarray(counts)[:-2].sum()) // 8)
        assert made == 1
    held = int(np.asarray(counts)[:-2].sum())
    if case == "overflow":
        assert held == n * min(cfg.moe_topk, cfg.n_experts)
        assert held > tfm.moe_tile(n, cfg) and made >= 2
    if case == "sparse":
        assert held == n * min(cfg.moe_topk, cfg.n_experts) > 512
        assert tfm.moe_tile(n, cfg) >= held and made == 1
    if case == "last_group":
        stacked, first = at_last_group(p, cfg)
        got, got_counts = run(x, stacked, cfg, first_group=first)
        same(got, y)
        np.testing.assert_array_equal(np.asarray(got_counts),
                                      np.asarray(counts))
    return made
