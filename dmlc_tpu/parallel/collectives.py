"""The collective surface: named XLA collectives over ICI/DCN.

This replaces the reference's socket-overlay data plane (tree allreduce /
ring recovery implemented downstream in rabit, topology computed by
/root/reference/tracker/dmlc_tracker/tracker.py:165-252).  On TPU there
is no overlay to compute: XLA lowers these ops onto the physical ICI
torus directly, so the "topology computation" the reference tracker does
in Python disappears into the compiler.

All functions are usable inside `jax.shard_map` / `pjit`-traced code and
are keyed by mesh axis *name* — the rank/world contract is the mesh
coordinate system (see parallel.mesh).  Dtype discipline: callers should
keep payloads bf16/f32; these wrappers do not cast.
"""

from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax

from ..base import get_env
from .. import telemetry

AxisName = Union[str, Sequence[str]]

# payload-size buckets for collective byte histograms: 64 B .. 8 GB
# (doubling) — latency buckets would be useless here, the wrappers run
# at TRACE time (see _note below)
BYTE_BOUNDS = tuple(64.0 * 2.0 ** i for i in range(28))


def _note(op: str, x, axis) -> None:
    """Telemetry for one collective call site.

    These wrappers execute while XLA TRACES the enclosing program (the
    device-side op runs later, inside the compiled step, where Python
    cannot observe it) — so what is knowable and recorded here is the
    static story: which collectives the program uses, over which axis,
    moving how many bytes per call.  That is exactly what the byte
    histograms and the per-op counters carry; wall-time skew between
    ranks comes from the host-side spans (TrackerClient collectives,
    feed/step spans) on the tracker's corrected /trace timeline, not
    from timing traced code."""
    try:
        nbytes = float(x.size * x.dtype.itemsize)
    except (AttributeError, TypeError):
        return  # abstract tracer without static shape: nothing to record
    telemetry.inc("collective", f"{op}_calls")
    telemetry.inc("collective", f"{op}_bytes", nbytes)
    telemetry.observe("collective", f"{op}_bytes_per_call", nbytes,
                      bounds=BYTE_BOUNDS)
    # a trace-time marker span: args carry the op/axis/byte tags so the
    # merged timeline shows WHAT was being traced/compiled when
    with telemetry.span(f"collective.{op}.trace", stage="collective",
                        args={"op": op, "axis": str(axis),
                              "bytes": int(nbytes)}):
        pass


def axis_size(axis: AxisName) -> int:
    """World size along ``axis`` (inside shard_map-traced code)."""
    return lax.axis_size(axis)


def axis_rank(axis: AxisName):
    """This shard's rank along ``axis`` (inside shard_map-traced code)."""
    return lax.axis_index(axis)


def all_reduce(x, axis: AxisName, op: str = "sum"):
    """All-reduce over a mesh axis.  op ∈ {sum, max, min, mean}.

    The TPU-native analog of rabit's tree+ring Allreduce; XLA emits the
    ICI-optimal reduction, no overlay required.
    """
    _note("all_reduce", x, axis)
    if op == "sum":
        return lax.psum(x, axis)
    if op == "max":
        return lax.pmax(x, axis)
    if op == "min":
        return lax.pmin(x, axis)
    if op == "mean":
        return lax.pmean(x, axis)
    raise ValueError(f"unknown reduce op: {op!r}")


def all_gather(x, axis: AxisName, *, tiled: bool = True, gather_axis: int = 0):
    """Gather shards along ``axis``; tiled=True concatenates on gather_axis."""
    _note("all_gather", x, axis)
    return lax.all_gather(x, axis, axis=gather_axis, tiled=tiled)


def reduce_scatter(x, axis: AxisName, *, scatter_axis: int = 0, tiled: bool = True):
    """Reduce-scatter: psum then keep this rank's shard of ``scatter_axis``."""
    _note("reduce_scatter", x, axis)
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_axis, tiled=tiled)


def broadcast(x, axis: AxisName, root: int = 0):
    """Broadcast ``root``'s value to every rank along ``axis``."""
    # Select root's contribution and sum: zero elsewhere.  XLA folds this
    # into an efficient broadcast; avoids gather-then-index materialising
    # the full world.
    _note("broadcast", x, axis)
    is_root = lax.axis_index(axis) == root
    contrib = jnp.where(is_root, x, jnp.zeros_like(x))
    return lax.psum(contrib, axis)


def ppermute_ring(x, axis: AxisName, shift: int = 1):
    """Rotate shards around the ring defined by ``axis`` (ICI neighbours).

    The building block for ring attention and pipeline schedules —
    replaces the reference tracker's explicitly-computed ring
    (tracker.py:193-225) with a compiler-lowered neighbour exchange.
    """
    _note("ppermute", x, axis)
    n = lax.axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)


def all_to_all(x, axis: AxisName, *, split_axis: int, concat_axis: int, tiled: bool = True):
    """All-to-all: re-shard from split_axis to concat_axis across ``axis``.

    Used for Ulysses-style sequence↔head re-sharding and MoE token
    routing.
    """
    _note("all_to_all", x, axis)
    return lax.all_to_all(
        x, axis, split_axis=split_axis, concat_axis=concat_axis, tiled=tiled
    )


def match_vma(x, ref):
    """Cast x's varying-manual-axes type up to ref's.

    Needed for loop carries under VMA-checked shard_map: an invariant
    initial accumulator that folds in device-varying values must be typed
    varying from the start.  No-op outside shard_map / when already
    varying on ref's axes.
    """
    want = jax.typeof(ref).vma - jax.typeof(x).vma
    if not want:
        return x
    return lax.pcast(x, tuple(want), to="varying")


def barrier_sum(axis: AxisName):
    """A cheap synchronisation point: psum of a scalar 1 (returns world size)."""
    telemetry.inc("collective", "barrier_sum_calls")
    return lax.psum(jnp.ones((), jnp.int32), axis)


# ---------------------------------------------------------------------------
# Host-level (multi-process) surface
# ---------------------------------------------------------------------------

def process_rank_world() -> tuple:
    """(rank, world) of this host process.

    Honours the DMLC env contract first (DMLC_TASK_ID / DMLC_NUM_WORKER,
    reference tracker.py:414-415 & yarn/ApplicationMaster.java:439-443) so
    jobs launched by dmlc-submit agree with jax.distributed; falls back to
    the JAX runtime's own notion.
    """
    task_id = get_env("DMLC_TASK_ID", None, str)
    nworker = get_env("DMLC_NUM_WORKER", None, str)
    if task_id is not None and nworker is not None:
        return int(task_id), int(nworker)
    return jax.process_index(), jax.process_count()


def initialize_distributed(coordinator: Optional[str] = None) -> None:
    """Bring up jax.distributed using the DMLC env contract.

    The coordinator is named by DMLC_JAX_COORD_URI/PORT, which the tracker
    allocates alongside its own socket (rendezvous.py submit_job) — NOT by
    DMLC_TRACKER_PORT: that port is the rabit tracker's already-bound
    listener (reference tracker.py:182-183), so rank 0 could never host
    the gRPC coordinator service there.  Rank/world come from
    process_rank_world() (DMLC_TASK_ID / DMLC_NUM_WORKER).  No-op when
    single-process or when jax.distributed is already up.
    """
    rank, world = process_rank_world()
    if world <= 1:
        return
    if jax.distributed.is_initialized():
        return
    if coordinator is None:
        uri = (get_env("DMLC_JAX_COORD_URI", "")
               or get_env("DMLC_TRACKER_URI", "127.0.0.1"))
        # no tracker-port fallback on purpose (see docstring), and no
        # made-up default either: tracker_host:<guess> can never be right
        # on multi-host jobs, so dialing it would trade a clear error for
        # a multi-minute gRPC hang
        port = get_env("DMLC_JAX_COORD_PORT", None, str)
        if port is None:
            raise RuntimeError(
                "DMLC_JAX_COORD_PORT is not set — this process was not "
                "launched by a tracker that allocates the jax.distributed "
                "coordinator (dmlc-submit does); pass "
                "coordinator='host:port' explicitly")
        coordinator = f"{uri}:{port}"
    jax.distributed.initialize(
        coordinator_address=coordinator, num_processes=world, process_id=rank
    )
