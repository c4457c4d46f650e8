#!/usr/bin/env python
"""The forward flash kernel alone, at the shapes the models call it with.

    chiprun -- python scripts/time_flash_fwd.py             # the chip
    python scripts/time_flash_fwd.py --interpret            # CPU smoke, toy sizes
    python scripts/time_flash_fwd.py --root .parent_check   # another checkout

Times ``flash_attention`` / ``block_attend_flash`` (kernels ``flash_fwd_o``
and ``flash_fwd`` of ``dmlc_tpu/ops/flash_attention.py``) and nothing
around them, case by case:

  a   Command A+'s full layer: a chunk of 8,192 rows at ``q_offset`` 24,576
      against 32,768 keys, 128 query heads on 8 K/V heads of 128
  b   the same under ``span=4096`` (its sliding layers)
  c   A.X-K1's prefill: T=16,384, 64 heads, qk 192, v 128
  d1  the flagship's ``flash_attention``: B=1, T=2,048, 16 heads of 128
  d2  the ring step's ``block_attend_flash``: B=2, Tq=Tk=4,096, 8 heads,
      blocks of 512, on the diagonal (offsets equal) and behind it

For each case it prints the kernel's time, the share of the roofline
that is (the operations ``benchmarks/costs*.py`` count over the chip's
peak, all of them compute-bound), and microseconds a tile: a Q block's
walk costs ``walk + unmasked x n_u + boundary x n_b``, and three probes
with the case's heads tell the three apart (a walk of one unmasked
tile, a walk of one boundary tile, a walk of ``LONG`` unmasked tiles).
A kernel's time is the sum of its device events in a profiler trace
(the transposes into its layout are other events); where the trace
shows none (``--interpret``: no device) it is the host's clock around
the whole call, and the line says so.  An observation for PERF.md,
never a benchmark metric.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time

LONG = 8  # unmasked tiles in the long probe's walk


def tiles(tq, tk, qoff, kvoff, bq, bk, causal=True, span=0):
    """(unmasked, boundary) tiles one (batch, head) pair's call computes,
    classified as ``_dispatch_masked_step`` classifies them."""
    bq, bk = min(bq, tq), min(bk, tk)
    clean = masked = 0
    for qi in range(-(-tq // bq)):
        first_q = qoff + qi * bq
        last_q = first_q + bq - 1
        for j in range(-(-tk // bk)):
            kb_first = kvoff + j * bk
            kb_last = kb_first + bk - 1
            visible = not causal or last_q >= kb_first
            boundary = causal and kb_last > first_q
            if span:
                visible = visible and kb_last > first_q - span
                boundary = boundary or kb_first <= last_q - span
            boundary = boundary or kb_last >= kvoff + tk
            if visible:
                masked += boundary
                clean += not boundary
    return clean, masked


def chunk_pairs(first, rows, window):
    """Visible (query, key) pairs of rows [first, first + rows) of a
    causal prompt, under a window or (0) without."""
    from benchmarks.costs_gqa_swa import visible_pairs

    return visible_pairs(first + rows, window) - visible_pairs(first, window)


def cases(toy: bool):
    """name -> dict(call=..., shapes, flops, tile counts, probe family)."""
    import jax.numpy as jnp

    from benchmarks import costs, costs_mla_moe
    from dmlc_tpu.ops import flash_attention as flash

    blk = dict(block_q=16, block_k=16) if toy else {}
    bq = 16 if toy else 1024
    dt = jnp.float32 if toy else jnp.bfloat16
    interp = dict(interpret=True) if toy else {}
    out = {}

    # a / b: grouped heads, a chunk of rows at an offset
    h, h_kv, d = (4, 2, 128) if toy else (128, 8, 128)
    rows, off, tk = (32, 32, 64) if toy else (8192, 24576, 32768)
    for name, span in (("a", 0), ("b", 32 if toy else 4096)):
        def call(q, k, v, _span=span):
            return flash.flash_attention(q, k, v, span=_span, q_offset=off,
                                         **blk, **interp)
        out[name] = dict(
            call=call, q=(1, rows, h, d), k=(1, tk, h_kv, d),
            v=(1, tk, h_kv, d), dtype=dt, heads=h,
            flops=4.0 * chunk_pairs(off, rows, span) * h * d,
            tiles=tiles(rows, tk, off, 0, bq, bq, span=span))

    def grouped_probe(tq, tk_, q_off, span=0):
        def call(q, k, v):
            return flash.flash_attention(q, k, v, span=span, q_offset=q_off,
                                         **blk, **interp)
        return dict(call=call, q=(1, tq, h, d), k=(1, tk_, h_kv, d),
                    v=(1, tk_, h_kv, d), dtype=dt, heads=h)

    out["a"]["probes"] = dict(
        one_unmasked=grouped_probe(bq, bq, bq),
        one_boundary=grouped_probe(bq, bq, 0),
        long_unmasked=grouped_probe(bq, LONG * bq, LONG * bq))
    out["b"]["probes"] = dict(
        out["a"]["probes"], one_boundary=grouped_probe(bq, bq, 0, bq // 2))

    # c: latent attention's prefill, v narrower than qk
    hc, qk, dv, t = (2, 192, 128, 64) if toy else (64, 192, 128, 16384)
    model = dict(n_heads=hc, qk_nope_head_dim=128, qk_rope_head_dim=64,
                 v_head_dim=dv, dtype="bfloat16")
    scale = 0.1147  # A.X-K1's, yarn's factor in it: any value times alike

    def mla(q_off):
        def call(q, k, v):
            return flash.flash_attention(q, k, v, scale=scale,
                                         q_offset=q_off, **blk, **interp)
        return call

    def mla_probe(tq, tk_, q_off):
        return dict(call=mla(q_off), q=(1, tq, hc, qk), k=(1, tk_, hc, qk),
                    v=(1, tk_, hc, dv), dtype=dt, heads=hc)

    out["c"] = dict(
        mla_probe(t, t, None),
        flops=costs_mla_moe.mla_prefill_attn_cost(model, t)["flops"],
        tiles=tiles(t, t, 0, 0, bq, bq),
        probes=dict(one_unmasked=mla_probe(bq, bq, bq),
                    one_boundary=mla_probe(bq, bq, 0),
                    long_unmasked=mla_probe(bq, LONG * bq, LONG * bq)))

    # d1: the flagship's differentiable call; d2: the ring step
    b1, t1, h1 = (1, 64, 2) if toy else (1, 2048, 16)
    out["d1"] = dict(
        call=lambda q, k, v: flash.flash_attention(q, k, v, **blk, **interp),
        q=(b1, t1, h1, 128), k=(b1, t1, h1, 128), v=(b1, t1, h1, 128),
        dtype=dt, heads=b1 * h1,
        flops=costs._attn_block_flops(b1, t1, t1, h1, 128, 2, 0.5),
        tiles=tiles(t1, t1, 0, 0, bq, bq))
    b2, t2, h2 = (1, 32, 2) if toy else (2, 4096, 8)
    rb = 8 if toy else 512

    def ring(q_off, kv_off, b=rb):
        def call(q, k, v):
            return flash.block_attend_flash(
                q, k, v, scale=128 ** -0.5, causal=True, q_offset=q_off,
                kv_offset=kv_off, block_q=b, block_k=b, **interp)
        return call

    def ring_case(q_off, kv_off, visible):
        return dict(call=ring(q_off, kv_off), q=(b2, t2, h2, 128),
                    k=(b2, t2, h2, 128), v=(b2, t2, h2, 128), dtype=dt,
                    heads=b2 * h2,
                    flops=costs._attn_block_flops(b2, t2, t2, h2, 128, 2,
                                                  visible),
                    tiles=tiles(t2, t2, q_off, kv_off, rb, rb))

    out["d2_diagonal"] = ring_case(t2, t2, 0.5)
    out["d2_behind"] = ring_case(t2, 0, 1.0)

    def mha_probe(tq, tk_, q_off, b):
        # the flagship's kernel through the ring step's entry, which
        # takes the offsets a probe needs
        return dict(call=ring(q_off, 0, b), q=(b2, tq, h2, 128),
                    k=(b2, tk_, h2, 128), v=(b2, tk_, h2, 128), dtype=dt,
                    heads=b2 * h2)

    out["d1"]["probes"] = dict(
        one_unmasked=mha_probe(bq, bq, bq, bq),
        one_boundary=mha_probe(bq, bq, 0, bq),
        long_unmasked=mha_probe(bq, LONG * bq, LONG * bq, bq))
    out["d2_diagonal"]["probes"] = dict(
        one_unmasked=mha_probe(rb, rb, rb, rb),
        one_boundary=mha_probe(rb, rb, 0, rb),
        long_unmasked=mha_probe(rb, LONG * rb, LONG * rb, rb))
    return out


def _kernel_events(trace_dir):
    """scope -> seconds of the flash kernels' device events under it."""
    from benchmarks import reduce_trace

    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        return {}
    trace = reduce_trace.load_xplane(max(found, key=os.path.getmtime))
    seconds = {}
    for events in trace.devices.values():
        for e in events:
            if "flash_fwd" not in e.path:
                continue
            scope = next((part for part in e.path.split("/")
                          if part.startswith("time_")), None)
            if scope:
                seconds[scope] = seconds.get(scope, 0.0) + e.seconds
    return seconds


def measure(todo, reps: int, trace: bool):
    """``todo``: label -> case-like dict.  Returns label -> (seconds a
    call, "device_trace" | "host_clock")."""
    import jax

    fns, host = {}, {}
    for n, (label, c) in enumerate(todo.items()):
        scope = f"time_{n}"

        def fn(q, k, v, _call=c["call"], _scope=scope):
            with jax.named_scope(_scope):
                out = _call(q, k, v)
            return out[0] if isinstance(out, tuple) else out

        keys = jax.random.split(jax.random.PRNGKey(n), 3)
        args = [jax.random.normal(key, c[name], c["dtype"])
                for key, name in zip(keys, "qkv")]
        jitted = jax.jit(fn)
        jax.block_until_ready(jitted(*args))  # compiles
        host[label] = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(jitted(*args))
            host[label] = min(host[label], time.perf_counter() - t0)
        if trace:
            fns[label] = (scope, jitted, args)
    if not trace:
        return {label: (s, "host_clock") for label, s in host.items()}
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for scope, jitted, args in fns.values():
                for _ in range(reps):
                    jax.block_until_ready(jitted(*args))
        by_scope = _kernel_events(trace_dir)
    out = {}
    for label, (scope, _, _) in fns.items():
        if by_scope.get(scope):
            out[label] = (by_scope[scope] / reps, "device_trace")
        else:
            out[label] = (host[label], "host_clock")
    return out


def report(name, case, times, peak_flops):
    """One case's lines as a dict: time, roofline share, us a tile."""
    seconds, source = times[name]
    n_u, n_b = case["tiles"]
    line = {"case": name, "ms": seconds * 1e3, "clock": source,
            "tiles_unmasked": n_u * case["heads"],
            "tiles_boundary": n_b * case["heads"],
            "boundary_tile_share": n_b / float(n_u + n_b),
            "roofline_share_pct": peak_flops and (
                100.0 * case["flops"] / peak_flops / seconds),
            "us_per_tile_mean": seconds * 1e6 / ((n_u + n_b) * case["heads"])}
    probes = {p: times.get(f"{name}.{p}") for p in case.get("probes", {})}
    if probes and all(probes.values()):
        walks = {p: case["probes"][p]["heads"] for p in probes}
        one_u = probes["one_unmasked"][0] / walks["one_unmasked"]
        one_b = probes["one_boundary"][0] / walks["one_boundary"]
        long_u = probes["long_unmasked"][0] / walks["long_unmasked"]
        t_u = (long_u - one_u) / (LONG - 1)
        line.update(us_unmasked_tile=t_u * 1e6,
                    us_boundary_tile=(one_b - one_u + t_u) * 1e6,
                    us_walk=(one_u - t_u) * 1e6)
    return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--interpret", action="store_true",
                    help="toy sizes through the Pallas interpreter: a smoke "
                         "run, its times mean nothing")
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="the checkout to time")
    ap.add_argument("--cases", default="a,b,c,d1,d2_diagonal,d2_behind")
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.abspath(args.root))

    import jax

    device = jax.devices()[0]
    if not args.interpret and device.platform != "tpu":
        raise SystemExit("needs a TPU (or --interpret for the smoke run)")
    if args.interpret:
        peak = None  # no chip, no roofline
    else:
        with open(os.path.join(args.root, "benchmarks", "peaks.json")) as f:
            peak = json.load(f)[device.device_kind]["bf16_flops_per_s"]
    table = cases(toy=args.interpret)
    wanted = [c for c in args.cases.split(",") if c in table]
    # a probe two cases share is one program and is timed once (the
    # compile cache would hand the second the first's executable, and
    # the trace would give the first both programs' events)
    todo, first_of, alias = {}, {}, {}
    for name in wanted:
        todo[name] = table[name]
        for p, probe in table[name].get("probes", {}).items():
            alias[f"{name}.{p}"] = first_of.setdefault(id(probe),
                                                       f"{name}.{p}")
            todo.setdefault(alias[f"{name}.{p}"], probe)
    times = measure(todo, args.reps, trace=not args.interpret)
    times.update({label: times[first] for label, first in alias.items()})
    lines = []
    for name in wanted:
        line = report(name, table[name], times, peak)
        line["device"] = device.device_kind
        lines.append(line)
        print(json.dumps(line), flush=True)
    return lines


if __name__ == "__main__":
    main()
