"""SPMD pipeline parallelism: GPipe-style microbatch schedule over the
``pp`` mesh axis using collective permutes.

New capability relative to the reference (which is data-parallel only,
SURVEY.md §2.7); designed the TPU way: every pp rank runs the same traced
program (no per-stage programs, no host scheduler), activations advance
one stage per step via `lax.ppermute` over ICI neighbours, and the bubble
is the standard M + P - 1 steps for M microbatches over P stages.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax


def pipeline_spmd(
    stage_fn: Callable,
    stage_params,
    x_microbatches,
    *,
    axis_name: str = "pp",
):
    """Run a P-stage pipeline inside shard_map.

    stage_fn(params, x) -> y must preserve the activation shape (standard
    transformer blocks do).  ``stage_params`` is this rank's stage's
    parameter pytree (stack the per-stage params on a leading axis and
    shard it over pp outside).  ``x_microbatches``: [M, mb, ...] — the
    full input, replicated or broadcast; only stage 0 consumes it.

    Returns [M, mb, ...] outputs, valid on every rank (broadcast from the
    last stage).
    """
    from .. import telemetry
    from .collectives import match_vma

    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    m = x_microbatches.shape[0]
    total = m + n - 1
    # the GPipe bubble is fully determined by the schedule: each stage
    # idles n-1 of the m+n-1 steps (warmup on early ranks, drain on late
    # ones).  Recorded at trace time — the device-side fori_loop is
    # opaque to Python — so the gauges describe the COMPILED schedule;
    # multiply bubble_fraction by measured step wall time (train.step
    # histograms) for bubble seconds per step.
    telemetry.inc("pipeline", "runs_traced")
    telemetry.set_gauge("pipeline", "stages", n)
    telemetry.set_gauge("pipeline", "microbatches", m)
    telemetry.set_gauge("pipeline", "bubble_steps_per_stage", n - 1)
    telemetry.set_gauge("pipeline", "bubble_fraction",
                        (n - 1) / total if total else 0.0)
    telemetry.observe("pipeline", "microbatches_per_run", float(m))
    # carries vary over the input's axes AND pp (my-dependent writes,
    # ppermuted state): match x's vma then add pp via `my`, which is
    # already pp-varying.
    state0 = match_vma(match_vma(jnp.zeros_like(x_microbatches[0]), x_microbatches), my)
    outputs0 = match_vma(match_vma(jnp.zeros_like(x_microbatches), x_microbatches), my)
    perm_fwd = [(j, (j + 1) % n) for j in range(n)]

    def step(t, carry):
        outputs, state = carry
        # stage 0 ingests microbatch t (clamped; steps past M reuse the
        # last microbatch but their results are never written)
        feed = x_microbatches[jnp.minimum(t, m - 1)]
        x_in = jnp.where(my == 0, feed, state)
        y = stage_fn(stage_params, x_in)
        out_idx = t - (n - 1)  # microbatch finishing at the last stage
        write = (my == n - 1) & (out_idx >= 0)
        idx = jnp.clip(out_idx, 0, m - 1)
        outputs = jnp.where(
            write, outputs.at[idx].set(y), outputs
        )
        state = lax.ppermute(y, axis_name, perm_fwd)
        return outputs, state

    outputs, _ = lax.fori_loop(0, total, step, (outputs0, state0))
    # broadcast finished outputs from the last stage to all pp ranks
    is_last = (my == n - 1)
    contrib = jnp.where(is_last, outputs, jnp.zeros_like(outputs))
    return lax.psum(contrib, axis_name)


def make_pipeline(mesh, stage_fn, *, axis_name: str = "pp"):
    """shard_map wrapper: params stacked on leading stage axis, sharded pp.

    The returned callable is span-wrapped (``pipeline.run``, tagged with
    stage count and microbatch count): host-side dispatch of each
    pipelined step lands on the flight-recorder timeline even though the
    stage loop itself runs device-side.
    """
    from jax.sharding import PartitionSpec as P

    from .. import telemetry

    def inner(params_stacked, x_mb):
        local = jax.tree.map(lambda p: p[0], params_stacked)
        return pipeline_spmd(stage_fn, local, x_mb, axis_name=axis_name)

    mapped = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(),
        check_vma=False,
    )
    n_stages = int(mesh.shape[axis_name])

    def run(params_stacked, x_mb):
        # tokens tag (additive): the step ledger and trace readers can
        # relate this dispatch to goodput without re-deriving shapes
        # (x_mb is [M, mb, T, ...] — tokens = M·mb·T when T is present)
        tokens = 1
        for d in x_mb.shape[:3]:
            tokens *= int(d)
        with telemetry.span("pipeline.run", stage="pipeline",
                            args={"stages": n_stages,
                                  "microbatches": int(x_mb.shape[0]),
                                  "tokens": tokens}):
            return mapped(params_stacked, x_mb)

    return run
