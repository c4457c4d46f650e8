#!/usr/bin/env python
"""XLA's grouped product alone, at the held experts' real stacks.

    python scripts/time_ragged_dot.py                       # on a TPU host
    python scripts/time_ragged_dot.py --toy                 # CPU smoke, toy sizes
    python scripts/time_ragged_dot.py --cells nemotron3     # some cells

``lax.ragged_dot`` is how ``models.transformer._moe_held_ffn`` runs the
experts held on a chip: the callers hand it every expert layer's stack
as ONE group axis, with the layer's own groups live and the rest empty.
For each catalog cell's stack it times two things:

  sweep  one call ``[M, E] x [G, E, F]`` with M rows spread over one
         layer's groups (the last layer's, so the empty groups come
         first), at M = 64 ... 16,384: what one call costs, and how
         that grows with its rows
  walk   a layer's held pairs through its grouped products as the
         layer's trip loop runs them (every matrix a trip, the groups
         cut at the trip's edges, the result written back): the same
         pairs in trips of ``tile`` rows, 512 against fewer and larger

  cell       groups  E x F                 matrices  walks (held pairs)
  axk1       6 x 12   7168 x 2048          3         4,096 / 8,192 (8k / 16k)
  ling3      12 x 64  2560 x 768           3         64 / 1,024 / 8,192
  cmdaplus   4 x 16   4096 x 4096          3         8 / 8,192 (a chunk of rows)
  nemotron3  5 x 128  1024 x 2688 (latent) 2 relu2   704 / 5,632 / 45,056

A case's time is its device events inside the host span around its
calls (a profiler trace; the events of XLA's ``ragged-dot`` carry no
name path, so the span is what ties them to the case), per call: the
busy union of all of them (``ms``) and the ``ragged-dot`` ops' own
(``ms_ragged``).  With ``--toy`` (no chip) it is the host's clock
around the call at toy sizes, and the line says so.  An observation
for PERF.md, never a benchmark metric.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time

SWEEP = (64, 512, 1024, 2048, 4096, 8192, 16384)

# name -> (layers, held experts a layer, E, F, matrices, walks: held
# pairs, tiles tried); a walk's largest tile is the whole layer (one trip)
CELLS = {
    "axk1": (6, 12, 7168, 2048, 3, ((4096, (512, 2048, 4096)),
                                    (8192, (512, 2048, 4096, 8192)))),
    "ling3": (12, 64, 2560, 768, 3, ((64, (512,)),
                                     (1024, (512, 1024)),
                                     (8192, (512, 2048, 4096, 8192)))),
    "cmdaplus": (4, 16, 4096, 4096, 3, ((8, (512,)),
                                        (8192, (512, 2048, 4096, 8192)))),
    "nemotron3": (5, 128, 1024, 2688, 2, ((704, (512, 704)),
                                          (5632, (512, 2048, 5632)),
                                          (45056, (512, 4096, 8192, 16384,
                                                   45056)))),
}
TOY = {name: (2, 4, 32, 48, mats, ((40, (16, 40)),))
       for name, (_, _, _, _, mats, _) in CELLS.items()}


def round8(n: int) -> int:
    return -(-n // 8) * 8


def spread(rows: int, live: int, first: int, groups: int):
    """Group sizes [groups]: ``rows`` over the ``live`` groups from
    ``first``, as even as whole rows allow; the others empty."""
    import numpy as np

    sizes = np.zeros(groups, np.int32)
    sizes[first:first + live] = rows // live
    sizes[first:first + rows % live] += 1
    return sizes


def stacks(cell, key):
    """The cell's expert stacks on the device, each [G, E, F] / [G, F, E]
    in bf16: one seeded block per matrix, scaled per group (random bits
    for the whole stack would need twice its bytes in temporaries)."""
    import jax
    import jax.numpy as jnp

    layers, live, e, f, mats, _ = cell
    g = layers * live
    made = []
    for m, shape in enumerate([(e, f)] * (mats - 1) + [(f, e)]):
        block = jax.random.normal(jax.random.fold_in(key, m), shape,
                                  jnp.bfloat16) * 0.02
        scale = 1.0 + jnp.arange(g, dtype=jnp.bfloat16) / g
        made.append(jax.jit(lambda b, s: b[None] * s[:, None, None])(
            block, scale))
    return made


def sweep_call():
    import jax
    from jax import lax

    def call(xs, ws, sizes):
        return lax.ragged_dot(xs, ws[0], sizes)
    return jax.jit(call)


def walk_call(held: int, tile: int, first: int, live: int, relu2: bool):
    """The layer's trip loop over ``held`` sorted pairs: each trip one
    grouped product per matrix on its rows, written back to its place."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    trips = -(-held // tile)

    def call(xs, ws, sizes):
        ends = jnp.cumsum(sizes[first:first + live])

        def one(i, y):
            lo = i * tile
            rows = lax.dynamic_slice_in_dim(xs, lo, tile)
            hi = jnp.clip(ends, lo, lo + tile)
            groups = jnp.zeros(sizes.shape[0], jnp.int32).at[
                first:first + live].set(
                    hi - jnp.concatenate([jnp.full((1,), lo, hi.dtype),
                                          hi[:-1]]))
            h = lax.ragged_dot(rows, ws[0], groups)
            h = jnp.square(jax.nn.relu(h)) if relu2 else \
                h * jax.nn.silu(lax.ragged_dot(rows, ws[1], groups))
            out = lax.ragged_dot(h, ws[-1], groups,
                                 preferred_element_type=jnp.float32)
            return lax.dynamic_update_slice_in_dim(y, out, lo, 0)

        return lax.fori_loop(0, trips, one, jnp.zeros(
            (trips * tile, ws[-1].shape[-1]), jnp.float32))
    return jax.jit(call)


def cases(cell, toy: bool):
    """label -> (jitted call, its row operand, its group sizes, the
    case's fields) for one cell; the stacks are the caller's."""
    import jax
    import jax.numpy as jnp

    layers, live, e, f, mats, walks = cell
    g = layers * live
    first = g - live
    out = {}
    for m in ((8, 16, 64) if toy else SWEEP):
        out[f"sweep.m{m}"] = (
            sweep_call(),
            jax.random.normal(jax.random.PRNGKey(m), (m, e), jnp.bfloat16),
            jnp.asarray(spread(m, live, first, g)),
            dict(kind="sweep", rows=m, groups=g, live=live,
                 matrix=f"{e}x{f}", calls=1))
    for held, tiles in walks:
        for tile in sorted({round8(min(t, held)) for t in tiles}):
            trips = -(-held // tile)
            out[f"walk.h{held}.t{tile}"] = (
                walk_call(held, tile, first, live, mats == 2),
                jax.random.normal(jax.random.PRNGKey(held),
                                  (trips * tile, e), jnp.bfloat16),
                jnp.asarray(spread(held, live, first, g)),
                dict(kind="walk", held=held, tile=tile, trips=trips,
                     calls=trips * mats, groups=g, live=live,
                     matrices=mats))
    return out


def _span_events(trace_dir, labels):
    """label -> (busy seconds, ragged-dot seconds) of the device events
    that start inside the host span of that label."""
    from benchmarks import reduce_trace

    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        return {}
    trace = reduce_trace.load_xplane(max(found, key=os.path.getmtime))
    spans = {e.name: e for e in trace.host if e.name in labels}
    out = {}
    for label, span in spans.items():
        evs = [e for d in trace.devices.values() for e in d
               if span.start <= e.start <= span.end]
        busy = reduce_trace.total(reduce_trace.union(
            (e.start, e.end) for e in evs))
        ragged = sum(s for e, s, _ in reduce_trace.self_times(evs)
                     if e.name.startswith("ragged-dot"))
        out[label] = (busy, ragged)
    return out


def measure(made, ws, reps: int, trace: bool):
    """made: label -> (jitted, rows, sizes, fields), each called on the
    stacks ``ws``.  Returns label -> the fields with the case's times
    per call."""
    import jax

    host = {}
    for label, (fn, x, sizes, _) in made.items():
        jax.block_until_ready(fn(x, ws, sizes))  # compiles
        host[label] = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x, ws, sizes))
            host[label] = min(host[label], time.perf_counter() - t0)
    dev = {}
    if trace:
        with tempfile.TemporaryDirectory() as trace_dir:
            with jax.profiler.trace(trace_dir):
                for label, (fn, x, sizes, _) in made.items():
                    with jax.profiler.TraceAnnotation(label):
                        for _ in range(reps):
                            jax.block_until_ready(fn(x, ws, sizes))
            dev = _span_events(trace_dir, set(made))
    out = {}
    for label, (_, _, _, fields) in made.items():
        line = {"case": label, **fields}
        if dev.get(label, (0, 0))[0] > 0:
            busy, ragged = dev[label]
            line.update(ms=busy * 1e3 / reps, ms_ragged=ragged * 1e3 / reps,
                        clock="device_trace")
        else:
            line.update(ms=host[label] * 1e3, clock="host_clock")
        line["ms_per_call"] = line["ms"] / fields["calls"]
        out[label] = line
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--toy", action="store_true",
                    help="toy sizes on whatever JAX finds: a smoke run, its "
                         "times mean nothing")
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default="",
                    help="also write the lines to this JSON-lines file")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    import jax

    device = jax.devices()[0]
    if not args.toy and device.platform != "tpu":
        raise SystemExit("needs a TPU (or --toy for the smoke run)")
    table = TOY if args.toy else CELLS
    lines = []
    for n, name in enumerate(c for c in args.cells.split(",") if c in CELLS):
        ws = stacks(table[name], jax.random.PRNGKey(n))
        made = cases(table[name], args.toy)
        for line in measure(made, ws, args.reps,
                            trace=not args.toy).values():
            line.update(cell=name, device=device.device_kind)
            lines.append(line)
            print(json.dumps(line), flush=True)
        del made, ws  # the next cell's stacks need the room
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(line) + "\n" for line in lines)
    return lines


if __name__ == "__main__":
    main()
