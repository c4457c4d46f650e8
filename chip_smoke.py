#!/usr/bin/env python3
"""chip_smoke.py: does the system still start on the chip?

Drives both main paths once, through the entry points a user calls, at
the full width of the flagship (models.flagship_config: L=16, d=2048,
16x128 heads, ff 6144, V=32768, bf16; random weights from a seed):

  device   platform tpu, kind, count, versions; the kind has a peaks
           row; the native parsers built
  kernels  every Pallas kernel compiles at the shapes the paths use and
           matches its lax reference (flash fwd/dkv/dq and the ring step
           inside shard_map; paged attention; the feed's on-device
           expansion + int32 bitcast)
  train    seeded RecordIO shards -> feed.recordio_feed ->
           make_train_step on a one-chip mesh: B=8 x T=1024 for five
           steps, then B=1 x T=8192 for two.  Run twice, each in its own
           process: the second finds train.step in the compile cache
  serve    bin/dmlc-serve --model flagship as a process, driven over
           HTTP by python -m dmlc_tpu.serving.loadgen in two prompt-
           length bands, then SIGTERM, drain, exit 0

One process per chip: this parent is stdlib-only and never imports jax;
each phase is a child that owns the chip alone, in sequence.  Any failed
phase is a nonzero exit; nothing is downgraded to a warning, and a run
that finds no chip fails.  Times printed are observations of a smoke,
never metrics.  The last stdout line of a chip run is exactly
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}};
what the phases observed is the "summary:" line above it and
chiprun_out/chip_smoke/summary.json.  A failed run and a dry run print
no such result.

  python chip_smoke.py                      one chip, the contract
  python chip_smoke.py --chips 4 --mesh dp=4 --mesh sp=2,tp=2
                                            trainer phase per mesh
  python chip_smoke.py --cpu-dryrun         toy width, interpret
                                            kernels; NOT A CHIP RUN
"""

import argparse
import json
import math
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, "chiprun_out", "chip_smoke")
BUDGET_S = 1150.0  # the contract allows 1200, compilation included
SEED = 20260926
# bf16 tolerance of kernel-vs-reference checks: max |got - want| over
# max(1, max |want|).  bf16 carries 8 significant bits (2**-8 = 0.4%);
# the kernels keep probabilities in f32 where the references round them
# to bf16, so a few percent of the largest value is expected and a
# wrong mask or offset shows up as O(1).
BF16_TOL = 0.05
LOSS_TOL = 0.05  # sharded vs one-chip step-0 loss, bf16 reduce order
# pallas_call names a compiled train step must contain (the ring step,
# sp > 1, has only the forward kernel: its backward is the lax twin)
MOSAIC_KERNELS = ("flash_fwd", "flash_dkv", "flash_dq")

_T0 = time.monotonic()


class SmokeFailure(Exception):
    pass


def need(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def say(args, msg):
    tag = "NOT A CHIP RUN " if args.cpu_dryrun else ""
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {tag}{msg}",
          flush=True)


# ---------------------------------------------------------------------
# children: each runs in its own process and owns the chip alone
# ---------------------------------------------------------------------

def _sizes(dry):
    """Shapes per phase.  The chip run is the flagship's; the dry run is
    a toy at the same head width (the kernels need D=128)."""
    if dry:
        return {
            "flash": [(2, 64, 2, 128), (1, 128, 2, 128)],
            "ring": [(2, 32, 2, 128)],
            "paged": dict(b=4, h=8, bs=16, d=128, n_blocks=32, w=4,
                          lengths=[1, 15, 16, 17]),
            "train": [(2, 64, 3), (1, 128, 2)],
        }
    return {
        "flash": [(8, 1024, 16, 128), (1, 8192, 16, 128)],
        # per-device ring-step shapes of the sp=2 x tp=2 run
        "ring": [(8, 512, 8, 128), (1, 4096, 8, 128)],
        "paged": dict(b=8, h=16, bs=16, d=128, n_blocks=256, w=66,
                      lengths=[1, 15, 16, 17, 33, 1023, 1024, 0]),
        "train": [(8, 1024, 5), (1, 8192, 2)],
    }


def _config(dry):
    from dmlc_tpu.models import TransformerConfig, flagship_config

    if dry:
        return TransformerConfig(
            vocab=512, d_model=64, n_heads=2, head_dim=128, d_ff=128,
            n_layers=2, n_experts=1, microbatches=1, dtype="float32",
            remat=True)
    return flagship_config()


def _kernels_forced(dry):
    """The dry run interprets the kernels; the chip run takes whatever
    ops/dispatch.py decides, and is then held to it."""
    import contextlib

    from dmlc_tpu.ops import dispatch

    return (dispatch.force_kernel_mode(dispatch.INTERPRET) if dry
            else contextlib.nullcontext())


def _need_chip_kernels(dry):
    """No lax path and no interpreter may have been reached."""
    from dmlc_tpu import telemetry

    k = telemetry.counters_snapshot().get("kernels", {})
    counts = {m: int(k.get(f"{m}_traces", 0))
              for m in ("mosaic", "interpret", "lax")}
    if dry:
        need(counts["interpret"] > 0 and counts["mosaic"] == 0,
             f"dry run kernels must be interpreted: {counts}")
    else:
        need(counts["mosaic"] > 0 and counts["interpret"] == 0
             and counts["lax"] == 0,
             f"a chip run reached a non-Mosaic path: {counts}")
    return counts


def _rel_err(got, want):
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    need(np.isfinite(got).all(), "kernel produced non-finite values")
    return float(np.max(np.abs(got - want))
                 / max(1.0, float(np.max(np.abs(want)))))


def child_device(args):
    import importlib.metadata as md

    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    versions = {p: md.version(p) for p in ("jax", "jaxlib")}
    try:
        versions["libtpu"] = md.version("libtpu")
    except md.PackageNotFoundError:
        versions["libtpu"] = None
    say(args, f"device: {dev} versions: {versions}")
    if not args.cpu_dryrun:
        need(dev["platform"] == "tpu",
             f"JAX found no accelerator: platform is {dev['platform']!r} "
             f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); "
             "chip_smoke.py only passes on a TPU")
    need(dev["count"] == args.chips,
         f"expected {args.chips} device(s), JAX reports {dev['count']}")
    pinned = sorted(k for k in os.environ if k.startswith("DMLC_PEAK_"))
    need(not pinned, f"peaks pinned by the environment: {pinned}")
    from dmlc_tpu import native
    from dmlc_tpu.telemetry import compute, steps

    if not args.cpu_dryrun:
        need(dev["kind"] in steps.DEVICE_PEAKS,
             f"device kind {dev['kind']!r} has no row in "
             "telemetry.steps.DEVICE_PEAKS")
        hbm = compute.sample_hbm(publish=False)
        need(hbm["source"] == "device",
             f"memory_stats() unavailable: HBM source {hbm['source']!r}")
    need(native.available(), "native parsers did not build (g++?)")
    return {"device": dev, "versions": versions}


def _one_device_mesh():
    from dmlc_tpu.parallel import build_mesh

    return build_mesh(1, dp=1, sp=1, tp=1, pp=1, ep=1)


def _check_flash(shape, mesh, interpret):
    """fwd + dkv + dq, called the way make_train_step calls them (inside
    a VMA-checked shard_map), against the materializing oracle run a few
    heads at a time so its [T, T] scores fit."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from dmlc_tpu.ops.flash_attention import flash_attention
    from dmlc_tpu.parallel.ring_attention import ring_attention_reference

    b, t, h, d = shape
    dtype = jnp.float32 if interpret else jnp.bfloat16
    q, k, v, do = (jax.random.normal(key, shape, dtype) for key in
                   jax.random.split(jax.random.PRNGKey(SEED), 4))

    def fwd_bwd(attend):
        def run(q, k, v, do):
            o, vjp = jax.vjp(attend, q, k, v)
            return (o,) + vjp(do)
        return run

    spec = P("dp", "sp", "tp", None)
    kernel = jax.jit(jax.shard_map(
        fwd_bwd(lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=interpret)),
        mesh=mesh, in_specs=(spec,) * 4, out_specs=(spec,) * 4)
    ).lower(q, k, v, do).compile()
    if not interpret:
        hlo = kernel.as_text()
        missing = [name for name in MOSAIC_KERNELS if name not in hlo]
        need(not missing, f"compiled program lacks kernels {missing}")
    got = jax.block_until_ready(kernel(q, k, v, do))
    oracle = jax.jit(fwd_bwd(
        lambda q, k, v: ring_attention_reference(q, k, v, causal=True)))
    hc = max(1, min(h, (1 << 29) // (b * t * t * 4)))
    want = [np.concatenate(parts, axis=2) for parts in zip(*(
        [np.asarray(x, np.float32) for x in oracle(
            *(a[:, :, i:i + hc] for a in (q, k, v, do)))]
        for i in range(0, h, hc)))]
    return {name: _rel_err(g, w)
            for name, g, w in zip(("o", "dq", "dk", "dv"), got, want)}


def _check_ring_step(shape, mesh, interpret):
    """block_attend_flash inside shard_map with traced global offsets:
    one diagonal (partly masked) and one fully visible block."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from dmlc_tpu.ops import flash_attention as flash

    b, t, h, d = shape
    dtype = jnp.float32 if interpret else jnp.bfloat16
    q, k, v = (jax.random.normal(key, shape, dtype) for key in
               jax.random.split(jax.random.PRNGKey(SEED + 1), 3))
    scale = d ** -0.5
    spec = P("dp", "sp", "tp", None)
    stat = P("dp", "tp", "sp")
    errs = {}
    for name, kv_blocks_back in (("diagonal", 0), ("visible", 1)):
        def offsets():
            # varying over sp, as the ring's my * t_local is
            qoff = (lax.axis_index("sp") + 1) * t
            return qoff, qoff - kv_blocks_back * t

        def kernel_step(q, k, v):
            qoff, kvoff = offsets()
            return flash.block_attend_flash(
                q, k, v, scale=scale, causal=True, q_offset=qoff,
                kv_offset=kvoff, interpret=interpret)

        def lax_step(q, k, v):
            qoff, kvoff = offsets()
            return flash._lax_block_attend(q, k, v, qoff, kvoff,
                                           scale=scale, causal=True)

        outs = []
        for step in (kernel_step, lax_step):
            pv, m, l = jax.jit(jax.shard_map(
                step, mesh=mesh, in_specs=(spec,) * 3,
                out_specs=(spec, stat, stat)))(q, k, v)
            o = pv / jnp.transpose(l, (0, 2, 1))[..., None]
            outs.append((o, m + jnp.log(l)))
        errs[name] = max(_rel_err(g, w) for g, w in zip(*outs))
    return errs


def _check_paged(s_w, p, interpret):
    import jax.numpy as jnp
    import numpy as np

    from dmlc_tpu.ops import paged_attention as paged

    b, h, bs, d, w = p["b"], p["h"], p["bs"], p["d"], p["w"]
    need(paged.supports(d, bs, h), "paged kernel gate refuses the shape")
    dtype = jnp.float32 if interpret else jnp.bfloat16
    lengths = np.asarray(p["lengths"][:b], np.int32)
    rng = np.random.default_rng(SEED)
    pools = [jnp.asarray(rng.standard_normal(
        (p["n_blocks"], bs, h, d)), dtype) for _ in range(2)]
    tables = np.stack([rng.permutation(p["n_blocks"])[:w]
                       for _ in range(b)]).astype(np.int32)
    q = jnp.asarray(rng.standard_normal((b, s_w, h, d)), dtype)
    got = paged.paged_attention(q, *pools, tables, lengths)
    want = paged._lax_paged_attention(q, *pools, tables, lengths,
                                      d ** -0.5)
    live = lengths > 0  # dead rows are garbage by contract
    return {"o": _rel_err(np.asarray(got, np.float32)[live],
                          np.asarray(want, np.float32)[live])}


def _write_records(path, payloads):
    from dmlc_tpu.io.recordio import RecordIOWriter
    from dmlc_tpu.io.stream import Stream

    with Stream.create(path, "w") as s:
        writer = RecordIOWriter(s)
        for payload in payloads:
            writer.write_record(payload)


def _check_feed_expand(mesh):
    """recordio_feed's packed transport: the jitted on-device expansion
    (uint8 dynamic_slice under vmap) against the host-padded feed on the
    same shard, then the consumer's bitcast to int32."""
    import jax
    import numpy as np

    from dmlc_tpu.feed import recordio_feed
    from dmlc_tpu.telemetry import compute

    rng = np.random.default_rng(SEED)
    n, max_bytes, batch = 96, 512, 32
    path = os.path.join(WORK, "expand.rec")
    _write_records(path, [
        rng.integers(0, 256, int(rng.integers(64, max_bytes + 1)),
                     dtype=np.uint8).tobytes() for _ in range(n)])
    kw = dict(batch_records=batch, max_bytes=max_bytes)
    padded = recordio_feed(path, mesh, **kw)
    packed = recordio_feed(path, mesh, pack_bytes=batch * max_bytes, **kw)
    to_int32 = jax.jit(lambda data: jax.lax.bitcast_convert_type(
        data.reshape(-1, max_bytes // 4, 4), jax.numpy.int32))
    batches = 0
    try:
        for want, got in zip(padded, packed):
            data = np.asarray(got["data"])
            need(np.array_equal(data, np.asarray(want["data"]))
                 and np.array_equal(np.asarray(got["length"]),
                                    np.asarray(want["length"])),
                 "on-device expansion differs from the host-padded feed")
            need(np.array_equal(np.asarray(to_int32(got["data"])),
                                data.view("<i4")),
                 "device bitcast to int32 differs from the host view")
            batches += 1
    finally:
        padded.close()
        packed.close()
    need(batches == n // batch, f"compared {batches} batches")
    stats = compute.sites()["feed.expand"].stats()
    need(stats["aot_fallbacks"] == 0, f"feed.expand fell back: {stats}")
    return {}  # exact or failed: nothing to bound


def child_kernels(args):
    """Settle each kernel on the chip against its lax reference before a
    model compiles around it, so a refusal names the kernel."""
    from dmlc_tpu.compile_cache import place_compile_cache
    from dmlc_tpu.ops import dispatch

    place_compile_cache()
    dry = args.cpu_dryrun
    sizes = _sizes(dry)
    mesh = _one_device_mesh()
    out = {}
    checks = (
        [(f"flash{shape}", _check_flash, (shape, mesh, dry))
         for shape in sizes["flash"]]
        + [(f"ring_step{shape}", _check_ring_step, (shape, mesh, dry))
           for shape in sizes["ring"]]
        + [(f"paged S={s_w}", _check_paged, (s_w, sizes["paged"], dry))
           for s_w in (1, 4)]
        + [("feed.expand", _check_feed_expand, (mesh,))])
    with _kernels_forced(dry):
        need(dispatch.kernel_mode() == (dispatch.INTERPRET if dry
                                        else dispatch.MOSAIC),
             f"kernel mode is {dispatch.kernel_mode()!r}")
        failed = []
        for name, check, cargs in checks:
            # every kernel gets its turn: one run names all that fail
            t0 = time.monotonic()
            try:
                res = check(*cargs)
                worst = max(res.values(), default=0.0)
                need(worst <= BF16_TOL,
                     f"rel err {worst:.4f} > {BF16_TOL}: {res}")
            except Exception as e:  # noqa: BLE001 - reported, then fatal
                print(f"kernel {name} FAILED: {str(e)[-3000:]}",
                      file=sys.stderr, flush=True)
                failed.append(name)
                continue
            say(args, f"kernel {name}: compiled and ran, rel err {res} "
                f"({time.monotonic() - t0:.1f}s observed)")
            out[name] = res
        need(not failed, f"kernels failed: {failed}")
    # the paged check goes through the dispatcher; the rest call the
    # kernels by name, which is not a dispatch decision
    out["kernel_traces"] = _need_chip_kernels(dry)
    return out


def _token_records(path, t, n_records, vocab):
    """Seeded learnable shard: arithmetic progressions over a 64-token
    alphabet spread across the vocabulary, T+1 int32 ids per record.  A
    model that merely learns which 64 ids occur drops from ln(V) toward
    ln(64) within a few steps."""
    import numpy as np

    rng = np.random.default_rng(SEED + t)
    alphabet = (np.arange(64) * (vocab // 64) + 7).astype(np.int32)
    payloads = []
    for _ in range(n_records):
        start, stride = rng.integers(0, 64), rng.integers(1, 7)
        payloads.append(
            alphabet[(start + stride * np.arange(t + 1)) % 64].tobytes())
    _write_records(path, payloads)


def _cache_entries(cache_dir):
    try:
        names = [n for n in os.listdir(cache_dir)
                 if not n.endswith("-atime")]
    except FileNotFoundError:
        names = []
    return {"dir": cache_dir, "entries": len(names),
            "train_step": sum("train_step" in n for n in names)}


def child_train(args):
    import jax
    import numpy as np

    from dmlc_tpu import native
    from dmlc_tpu.compile_cache import place_compile_cache
    from dmlc_tpu.feed import recordio_feed
    from dmlc_tpu.models import (init_params, make_train_step,
                                 param_specs, unsharded_loss)
    from dmlc_tpu.parallel import build_mesh
    from dmlc_tpu.telemetry import compute

    dry = args.cpu_dryrun
    cache_dir = place_compile_cache()
    cache_before = _cache_entries(cache_dir)
    axes = dict(dp=1, sp=1, tp=1, pp=1, ep=1)
    axes.update(args.mesh_axes)
    mesh = build_mesh(args.chips, **axes)
    cfg = _config(dry)
    n_parts = axes["dp"] * axes["sp"]
    need(native.available(), "native parsers did not build")

    out = {"mesh": axes, "shapes": []}
    with _kernels_forced(dry):
        params = init_params(jax.random.PRNGKey(SEED), cfg,
                             n_stages=axes["pp"])
        step, init_state = make_train_step(mesh, cfg)
        opt_state = None
        for b, t, n_steps in _sizes(dry)["train"]:
            if b % n_parts:
                # the feed gives every (dp, sp) coordinate its own rows;
                # keep the one-chip shape's tokens per device
                b *= n_parts
            path = os.path.join(WORK, f"tokens_T{t}.rec")
            # 4x the records the steps need: the byte-range partitions
            # are only roughly equal
            _token_records(path, t, 4 * b * n_steps, cfg.vocab)
            feed = recordio_feed(path, mesh, batch_records=b // n_parts,
                                 max_bytes=(t + 1) * 4)
            losses, step_s = [], []
            try:
                for batch in feed:
                    if np.any(np.asarray(batch["length"]) == 0):
                        continue  # epoch-tail padding
                    toks = jax.lax.bitcast_convert_type(
                        batch["data"].reshape(-1, t + 1, 4),
                        jax.numpy.int32)
                    ids, labels = toks[:, :-1], toks[:, 1:]
                    if opt_state is None:
                        if args.chips > 1:
                            # the same batch on one chip, before the
                            # sharded state exists
                            out["one_chip_loss"] = float(jax.jit(
                                unsharded_loss, static_argnums=3)(
                                    params, np.asarray(ids),
                                    np.asarray(labels), cfg))
                        # sharded as the step will return them, so no
                        # unsharded copy of the state waits on device 0
                        params = jax.device_put(params, jax.tree.map(
                            lambda spec: jax.sharding.NamedSharding(
                                mesh, spec), param_specs()))
                        opt_state = init_state(params)
                    t0 = time.monotonic()
                    params, opt_state, loss = step(params, opt_state,
                                                   ids, labels)
                    loss = float(jax.block_until_ready(loss))
                    step_s.append(round(time.monotonic() - t0, 3))
                    losses.append(loss)
                    need(math.isfinite(loss),
                         f"loss {loss} at B={b} T={t} step {len(losses)}")
                    if len(losses) == n_steps:
                        break
            finally:
                feed.close()
            need(len(losses) == n_steps,
                 f"feed ended after {len(losses)} of {n_steps} steps")
            say(args, f"train B={b} T={t}: losses "
                f"{[round(x, 4) for x in losses]}; step seconds observed "
                f"(first includes compile) {step_s}")
            out["shapes"].append({"B": b, "T": t, "losses": losses,
                                  "step_s_observed": step_s})
    first = out["shapes"][0]["losses"]
    need(abs(first[0] - math.log(cfg.vocab)) < 0.5,
         f"first loss {first[0]:.4f} not within 0.5 of ln({cfg.vocab})")
    need(first[-1] < first[0], f"loss did not fall: {first}")
    if "one_chip_loss" in out:
        need(abs(first[0] - out["one_chip_loss"]) <= LOSS_TOL,
             f"step-0 loss {first[0]:.4f} on mesh {axes} != one-chip "
             f"{out['one_chip_loss']:.4f} (tol {LOSS_TOL})")

    site = compute.sites()["train.step"]
    stats = site.stats()
    need(stats["aot_fallbacks"] == 0, f"train.step fell back: {stats}")
    out["compile_s_observed"] = stats["compile_secs_total"]
    out["signatures"] = stats["signatures"]
    if not dry:
        # the first shape is the first cache entry (dicts keep order)
        hlo = next(iter(site._cache.values()))[0].as_text()
        need("tpu_custom_call" in hlo,
             "no Mosaic custom call in the compiled train.step")
        want = MOSAIC_KERNELS[:1] if axes["sp"] > 1 else MOSAIC_KERNELS
        missing = [k for k in want if k not in hlo]
        need(not missing, f"compiled train.step lacks kernels {missing}")
        out["mosaic_kernels"] = list(want)
    out["kernel_traces"] = _need_chip_kernels(dry)

    hbm = compute.sample_hbm(publish=False)
    if not dry:
        need(hbm["source"] == "device",
             f"HBM sample came from {hbm['source']!r}")
    out["hbm_source"] = hbm["source"]
    out["bytes_in_use"] = {
        d.id: (d.memory_stats() or {}).get("bytes_in_use")
        for d in jax.local_devices()}
    say(args, f"bytes_in_use per device: {out['bytes_in_use']}")
    device_sets = set()
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        ids_ = sorted(d.id for d in leaf.sharding.device_set)
        device_sets.add(tuple(ids_))
        say(args, f"params{jax.tree_util.keystr(path)}: "
            f"{leaf.sharding.spec} on devices {ids_}")
    if args.chips > 1:
        need(device_sets != {(0,)}, "the weight is on device 0 alone")
        used = [v for v in out["bytes_in_use"].values() if v]
        need(dry or len(used) == args.chips,
             f"devices holding bytes: {out['bytes_in_use']}")
    out["cache_before"] = cache_before
    out["cache_after"] = _cache_entries(cache_dir)
    say(args, f"compile cache {cache_dir}: entries "
        f"{cache_before['entries']} -> {out['cache_after']['entries']} "
        f"(train_step {cache_before['train_step']} -> "
        f"{out['cache_after']['train_step']}); train.step compile seconds "
        f"observed {out['compile_s_observed']:.1f}")
    return out


CHILDREN = {"device": child_device, "kernels": child_kernels,
            "train": child_train}


def child_main(args):
    sys.path.insert(0, ROOT)
    os.makedirs(WORK, exist_ok=True)
    try:
        result = CHILDREN[args.child](args)
    except SmokeFailure as e:
        print(f"chip_smoke[{args.child}] FAILED: {e}", file=sys.stderr,
              flush=True)
        return 1
    with open(args.result, "w") as f:
        json.dump(result, f)
    return 0


# ---------------------------------------------------------------------
# parent: stdlib only, never holds a backend
# ---------------------------------------------------------------------

def left():
    return BUDGET_S - (time.monotonic() - _T0)


def child_env(args):
    env = dict(os.environ)
    if args.cpu_dryrun:
        env["JAX_PLATFORMS"] = "cpu"
        # a toy compiles in under JAX's one-second caching threshold
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}")
    return env


def run_child(args, phase, tag, extra=()):
    """One phase in its own process; returns its result document."""
    need("jax" not in sys.modules, "the parent must never import jax")
    need(left() > 30, f"out of time before phase {tag}")
    result = os.path.join(WORK, f"{tag}.json")
    if os.path.exists(result):
        os.unlink(result)
    cmd = [sys.executable, os.path.abspath(__file__), "--child", phase,
           "--result", result, "--chips", str(args.chips)]
    cmd += ["--cpu-dryrun"] if args.cpu_dryrun else []
    cmd += list(extra)
    t0 = time.monotonic()
    try:
        rc = subprocess.run(cmd, env=child_env(args), cwd=ROOT,
                            timeout=left()).returncode
    except subprocess.TimeoutExpired:
        raise SmokeFailure(f"phase {tag} ran out of time") from None
    need(rc == 0, f"phase {tag} failed (exit {rc})")
    with open(result) as f:
        doc = json.load(f)
    say(args, f"phase {tag} ok in {time.monotonic() - t0:.1f}s observed")
    return doc


def http_json(url, doc=None, timeout=600.0):
    data = None if doc is None else json.dumps(doc).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def scrape_metrics(url):
    with urllib.request.urlopen(url + "/metrics", timeout=30) as resp:
        text = resp.read().decode()
    out = {}
    for line in text.splitlines():
        name, _, value = line.partition(" ")
        if name and not name.startswith("#") and "{" not in name:
            try:
                out[name] = float(value)
            except ValueError:
                pass
    return out


def run_loadgen(args, url, band, streams, vocab, max_tokens):
    """The load generator is its own process.  It imports jax (through
    dmlc_tpu.serving) but needs no device, and is held to the CPU so it
    can never reach for the chip the server owns."""
    cmd = [sys.executable, "-m", "dmlc_tpu.serving.loadgen", "--url", url,
           "--streams", str(streams), "--requests-per-stream", "2",
           "--prompt-len", str(band[0]), str(band[1]),
           "--max-tokens", str(max_tokens), "--vocab", str(vocab),
           "--seed", str(SEED % 1000)]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run(cmd, env=env, cwd=ROOT, timeout=left(),
                              capture_output=True, text=True)
    except subprocess.TimeoutExpired:
        raise SmokeFailure(f"loadgen band {band} ran out of time") \
            from None
    lines = proc.stdout.strip().splitlines()
    need(lines, f"loadgen printed nothing: {proc.stderr[-2000:]}")
    summary = json.loads(lines[-1])
    need(proc.returncode == 0 and summary["n_requests_failed"] == 0
         and summary["n_requests_ok"] == 2 * streams,
         f"loadgen band {band}: {summary}")
    say(args, f"serve band {band[0]}-{band[1]}: "
        f"{summary['n_requests_ok']} requests ok, 0 failed, "
        f"{summary['total_generated_tokens']} tokens in "
        f"{summary['wall_s']:.1f}s observed (compiles included)")
    return summary


def phase_serve(args):
    """bin/dmlc-serve as a process, driven over HTTP, then SIGTERM."""
    need("jax" not in sys.modules, "the parent must never import jax")
    dry = args.cpu_dryrun
    # (prompt band, streams): each band pads to ONE prefill bucket; the
    # long band is sized to the default 256 x 16-token KV pool (two
    # ~1056-token requests in flight)
    bands = ([((9, 16), 4), ((49, 64), 2)] if dry
             else [((49, 64), 8), ((1009, 1024), 2)])
    model, vocab, max_tokens = (("tiny", 512, 8) if dry
                                else ("flagship", 32768, 32))
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    url = f"http://127.0.0.1:{port}"
    log_path = os.path.join(WORK, "dmlc-serve.log")
    t0 = time.monotonic()
    with open(log_path, "w") as log:
        server = subprocess.Popen(
            [sys.executable, os.path.join(ROOT, "bin", "dmlc-serve"),
             "--model", model, "--port", str(port), "--seed", str(SEED)],
            env=child_env(args), cwd=ROOT, stdout=log,
            stderr=subprocess.STDOUT)
    try:
        while True:
            need(server.poll() is None,
                 f"dmlc-serve exited {server.returncode} during start-up; "
                 f"see {log_path}")
            need(left() > 60, "dmlc-serve did not come up in time")
            try:
                http_json(url + "/healthz", timeout=5)
                break
            except (urllib.error.URLError, OSError):
                time.sleep(1.0)
        say(args, f"serve: {model} model up after "
            f"{time.monotonic() - t0:.1f}s observed")
        # the same prompt twice, alone each time: same program, same
        # inputs, so the same tokens
        prompt = [(7 * i + 3) % vocab for i in range(bands[0][0][1])]
        twice = [http_json(url + "/generate",
                           {"prompt": prompt, "max_tokens": max_tokens})
                 for _ in range(2)]
        need(not twice[0]["error"] and not twice[1]["error"]
             and twice[0]["n_generated"] == max_tokens
             and twice[0]["output_ids"] == twice[1]["output_ids"],
             f"same prompt, different answers: {twice}")
        out = {"bands": [run_loadgen(args, url, band, streams, vocab,
                                     max_tokens)
                         for band, streams in bands]}
        metrics = scrape_metrics(url)
        compute = http_json(url + "/compute")
        checks = {
            "paged_decode_steps":
                metrics.get("dmlc_serving_paged_decode_steps", 0),
            "crash_requeues": metrics.get("dmlc_serving_crash_requeues", 0),
            "nonfinite_failures":
                metrics.get("dmlc_serving_nonfinite_failures", 0),
            "http_503": metrics.get("dmlc_serving_http_503", 0),
            "aot_fallbacks_total": compute["aot_fallbacks_total"],
            "hbm_source": compute["hbm"]["source"],
            "kernel_traces": {
                m: metrics.get(f"dmlc_kernels_{m}_traces", 0)
                for m in ("mosaic", "interpret", "lax")},
            "decode_signatures":
                compute["sites"]["serving.decode_paged"]["signatures"],
            "prefill_signatures":
                compute["sites"]["serving.prefill"]["signatures"],
        }
        say(args, f"serve: {checks}")
        need(checks["paged_decode_steps"] > 0, "no paged decode step ran")
        need(checks["crash_requeues"] == 0
             and checks["nonfinite_failures"] == 0
             and checks["http_503"] == 0
             and checks["aot_fallbacks_total"] == 0,
             f"the server hid a failure: {checks}")
        need(checks["prefill_signatures"] == len(bands),
             f"each band must pad to one prefill bucket: {checks}")
        traces = checks["kernel_traces"]
        if dry:  # the tiny model is below the kernels' width gate
            need(traces["mosaic"] == 0, f"kernel traces: {traces}")
        else:
            need(checks["hbm_source"] == "device",
                 f"HBM source {checks['hbm_source']!r}")
            need(traces["mosaic"] > 0 and traces["lax"] == 0
                 and traces["interpret"] == 0,
                 f"serving reached a non-Mosaic path: {traces}")
        out["checks"] = checks
        server.send_signal(signal.SIGTERM)
        try:
            rc = server.wait(timeout=min(90.0, max(left(), 1.0)))
        except subprocess.TimeoutExpired:
            raise SmokeFailure("dmlc-serve did not drain after SIGTERM") \
                from None
        need(rc == 0, f"dmlc-serve exited {rc} after SIGTERM")
        say(args, f"phase serve ok in {time.monotonic() - t0:.1f}s "
            "observed (SIGTERM drained, exit 0)")
        return out
    finally:
        if server.poll() is None:
            server.kill()
            server.wait()


def parent_main(args):
    need(os.path.isdir(os.path.join(ROOT, "dmlc_tpu")),
         f"{ROOT} holds chip_smoke.py but not the dmlc_tpu package")
    os.makedirs(WORK, exist_ok=True)
    summary = {"ok": False}
    dev = run_child(args, "device", "device")
    summary["device"] = dev["device"]
    summary["versions"] = dev["versions"]
    say(args, f"device {dev['device']} {dev['versions']}")
    if args.chips == 1:
        summary["kernels"] = run_child(args, "kernels", "kernels")
        summary["train"] = [run_child(args, "train", f"train{i}")
                            for i in (1, 2)]
        cold, warm = summary["train"]
        need(warm["cache_after"]["train_step"]
             == cold["cache_after"]["train_step"] > 0,
             "the second train child added train.step cache entries: "
             f"{cold['cache_after']} -> {warm['cache_after']}")
        # a cache placed from outside (JAX_COMPILATION_CACHE_DIR) may
        # already hold this train.step from an earlier call: then the
        # first child adds nothing either, and there is no cold time to
        # beat
        prewarmed = (cold["cache_after"]["train_step"]
                     == cold["cache_before"]["train_step"])
        need(prewarmed
             or warm["compile_s_observed"] < cold["compile_s_observed"],
             "train.step compiled no faster from the cache: "
             f"{cold['compile_s_observed']:.1f}s then "
             f"{warm['compile_s_observed']:.1f}s")
        say(args, "compile cache: train.step "
            f"{cold['compile_s_observed']:.1f}s "
            + ("from a cache an earlier run warmed, " if prewarmed
               else "cold, ") +
            f"{warm['compile_s_observed']:.1f}s warm (observed), "
            f"{warm['cache_after']['train_step']} train_step entries of "
            f"{warm['cache_after']['entries']}, none added by the second "
            "child")
        summary["serve"] = phase_serve(args)
    else:
        # four chips: the trainer phase per mesh, one process over all
        # local chips each; serving stays single-chip
        summary["train"] = [
            run_child(args, "train", "train_" + spec.replace(",", "_"),
                      ["--mesh", spec])
            for spec in (args.mesh or ["dp=%d" % args.chips])]
    summary["ok"] = not args.cpu_dryrun
    if args.cpu_dryrun:
        summary["cpu_dryrun"] = "NOT A CHIP RUN"
    summary["elapsed_s_observed"] = round(time.monotonic() - _T0, 1)
    summary["claim"] = None
    return summary


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--mesh", action="append",
                    help="with --chips 4: axis sizes of one trainer run, "
                    "e.g. dp=4 or sp=2,tp=2 (repeatable)")
    ap.add_argument("--cpu-dryrun", action="store_true",
                    help="toy width on the CPU with interpreted kernels; "
                    "every line says NOT A CHIP RUN")
    ap.add_argument("--child", choices=sorted(CHILDREN),
                    help=argparse.SUPPRESS)
    ap.add_argument("--result", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        args.mesh_axes = {
            k: int(v) for k, v in
            (kv.split("=") for kv in (args.mesh or [""])[0].split(",")
             if kv)}
        return child_main(args)
    try:
        summary = parent_main(args)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    # what each phase observed: a file, and one line above the result
    with open(os.path.join(WORK, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    say(args, "summary: " + json.dumps(summary))
    if not args.cpu_dryrun:
        # the contract's result, and nothing after it: exactly these keys
        device = {k: summary["device"][k]
                  for k in ("platform", "kind", "count")}
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
