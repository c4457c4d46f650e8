#!/usr/bin/env python
"""Benchmarks vs the reference, printed as ONE JSON line on stdout.

Primary metric (vs_baseline is measured, same-hardware, same-file):
  recordio_inputsplit_read_MBps — the #1 hot path (SURVEY.md §3.1),
  measured the way the reference's own harness does
  (test/split_read_test.cc): iterate every record of a RecordIO file
  through InputSplit.  The baseline is the reference C++ compiled from
  /root/reference on this machine reading the same file (which our
  writer produced — every run re-proves bit-exact format compat).

extra_metrics:
  indexed_shuffled_read_MBps — shuffled IndexedRecordIO batch reads,
      ours vs the reference's indexed path (vs in
      indexed_shuffled_vs_baseline).
  transformer_tokens_per_s / transformer_mfu_pct — full AdamW train
      step of the flagship 1B bf16 LM (models.flagship_config) on the
      real chip; MFU = tokens/s × train FLOPs/token ÷ chip peak
      (causal-halved attention accounting, models.train_flops_per_token).
  recordio_feed_to_hbm_MBps — RecordIO payload bytes landed in device
      HBM per second via feed.recordio_feed (BASELINE config #2).
"""

import json
import os
import subprocess
import sys
import time

WORK = "/tmp/dmlc_tpu_bench"
DATA = os.path.join(WORK, "data.rec")
INDEX = os.path.join(WORK, "data.idx")
TARGET_PAYLOAD = 128 << 20  # 128 MB
TRIALS = 3

REF_MAIN = r"""
#include <dmlc/io.h>
#include <dmlc/timer.h>
#include <cstdio>
#include <cstring>
#include <memory>
int main(int argc, char *argv[]) {
  if (argc < 2) { fprintf(stderr, "usage: prog uri [index_uri]\n"); return 1; }
  std::unique_ptr<dmlc::InputSplit> split(
      argc > 2 ? dmlc::InputSplit::Create(argv[1], argv[2], 0, 1,
                                          "indexed_recordio", true, 0, 256)
               : dmlc::InputSplit::Create(argv[1], 0, 1, "recordio"));
  dmlc::InputSplit::Blob blob;
  double start = dmlc::GetTime();
  size_t bytes = 0, n = 0;
  while (split->NextRecord(&blob)) { bytes += blob.size; ++n; }
  double dt = dmlc::GetTime() - start;
  printf("%.3f %zu %zu\n", bytes / 1.0e6 / dt, bytes, n);
  return 0;
}
"""

REF_SOURCES = [
    "src/io.cc",
    "src/io/input_split_base.cc",
    "src/io/line_split.cc",
    "src/io/recordio_split.cc",
    "src/io/indexed_recordio_split.cc",
    "src/io/local_filesys.cc",
    "src/io/filesys.cc",
    "src/recordio.cc",
]


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def repo_path():
    return os.path.dirname(os.path.abspath(__file__))


def ensure_data():
    if (os.path.exists(DATA) and os.path.getsize(DATA) > TARGET_PAYLOAD
            and os.path.exists(INDEX)):
        return
    import numpy as np

    sys.path.insert(0, repo_path())
    from dmlc_tpu.io.recordio import RecordIOWriter
    from dmlc_tpu.io.stream import Stream

    log(f"bench: writing {TARGET_PAYLOAD >> 20} MB RecordIO to {DATA}")
    rng = np.random.default_rng(0)
    with Stream.create(DATA, "w") as s:
        w = RecordIOWriter(s)
        total = 0
        while total < TARGET_PAYLOAD:
            n = int(rng.integers(32 << 10, 96 << 10))
            w.write_record(rng.integers(0, 256, n, dtype=np.uint8).tobytes())
            total += n

    # index file (record head offsets) via the span scanner — the same
    # format the reference's ReadIndexFile consumes: "<index> <offset>".
    # _chunk_spans falls back to a Python header walk without the .so.
    from dmlc_tpu.feed.device_feed import _chunk_spans

    with open(DATA, "rb") as f:
        buf = f.read()
    sp = _chunk_spans(memoryview(buf))
    with open(INDEX, "w") as f:
        for i, (off, _ln, flag) in enumerate(sp.tolist()):
            head = off - 8 if flag == 0 else off
            f.write(f"{i} {head}\n")


# cache key includes the harness source: a stale binary from an earlier
# bench version would silently measure the wrong reference path
import hashlib

REFBIN = os.path.join(
    WORK, "refbench_" + hashlib.md5(REF_MAIN.encode()).hexdigest()[:10])


def ensure_refbin():
    if os.path.exists(REFBIN):
        return True
    main_cc = os.path.join(WORK, "ref_main.cc")
    with open(main_cc, "w") as f:
        f.write(REF_MAIN)
    cmd = (
        ["g++", "-O3", "-std=c++11", "-I/root/reference/include",
         "-DDMLC_USE_HDFS=0", "-DDMLC_USE_S3=0", "-DDMLC_USE_AZURE=0",
         main_cc]
        + [os.path.join("/root/reference", s) for s in REF_SOURCES]
        + ["-o", REFBIN, "-pthread"]
    )
    log("bench: compiling reference baseline harness")
    r = subprocess.run(cmd, capture_output=True, text=True)
    if r.returncode != 0:
        log(f"bench: reference build failed:\n{r.stderr[:2000]}")
        return False
    return True


def run_reference(indexed=False):
    best = 0.0
    args = [REFBIN, DATA] + ([INDEX] if indexed else [])
    for _ in range(TRIALS):
        out = subprocess.run(
            args, capture_output=True, text=True, check=True
        ).stdout.split()
        best = max(best, float(out[0]))
    return best


def run_ours():
    sys.path.insert(0, repo_path())
    from dmlc_tpu.io import input_split

    best = 0.0
    for _ in range(TRIALS):
        split = input_split.create(DATA, 0, 1, "recordio")
        t0 = time.perf_counter()
        nbytes = 0
        while True:
            rec = split.next_record()
            if rec is None:
                break
            nbytes += len(rec)
        dt = time.perf_counter() - t0
        split.close()
        best = max(best, nbytes / 1.0e6 / dt)
    return best


def run_ours_indexed_shuffled():
    sys.path.insert(0, repo_path())
    from dmlc_tpu.io import input_split

    best = 0.0
    for _ in range(TRIALS):
        split = input_split.create(
            DATA, 0, 1, "indexed_recordio", index_uri=INDEX, shuffle=True,
            seed=0, batch_size=256)
        t0 = time.perf_counter()
        nbytes = 0
        while True:
            rec = split.next_record()
            if rec is None:
                break
            nbytes += len(rec)
        dt = time.perf_counter() - t0
        split.close()
        best = max(best, nbytes / 1.0e6 / dt)
    return best


def bench_transformer():
    """Flagship 1B bf16 LM: full AdamW train step on the real chip."""
    import functools

    import jax
    import jax.numpy as jnp
    import optax

    sys.path.insert(0, repo_path())
    from dmlc_tpu.models import (flagship_config, init_params,
                                 train_flops_per_token, unsharded_loss)

    if jax.devices()[0].platform != "tpu":
        log("bench: no TPU visible, skipping transformer bench")
        return None

    import contextlib

    from dmlc_tpu import metrics

    from dmlc_tpu import telemetry

    cfg = flagship_config()
    opt = optax.adamw(1e-4)
    kind = jax.devices()[0].device_kind
    # dense bf16 peak FLOP/s per chip — one table shared with the step
    # ledger's MFU accounting (DMLC_PEAK_FLOPS overrides both)
    peak = telemetry.detect_peak_flops()

    def measure(B, T, n_steps):
        params = init_params(jax.random.PRNGKey(0), cfg, n_stages=1)
        opt_state = opt.init(params)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def step(p, s, ids, labels):
            loss, g = jax.value_and_grad(
                lambda p_: unsharded_loss(p_, ids, labels, cfg))(p)
            up, s = opt.update(g, s, p)
            return optax.apply_updates(p, up), s, loss

        ids = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                                 cfg.vocab)
        labels = jnp.roll(ids, -1, axis=1)
        for _ in range(2):  # compile + settle
            params, opt_state, loss = step(params, opt_state, ids, labels)
        # the clock brackets float(loss) value fetches: each waits for
        # the whole chain of steps behind it
        float(loss)
        trace_dir = os.environ.get("DMLC_BENCH_TRACE")
        fpt = train_flops_per_token(cfg, T, causal=True)
        telemetry.reset_steps()  # ledger records for THIS run only
        with contextlib.ExitStack() as stack:
            if trace_dir:  # guarantees stop_trace even on a failing step
                stack.enter_context(jax.profiler.trace(trace_dir))
                log(f"bench: capturing jax profiler trace to {trace_dir}")
            t0 = time.perf_counter()
            for _ in range(n_steps):
                telemetry.step_begin()
                with metrics.annotate("dmlc_train_step"):
                    params, opt_state, loss = step(params, opt_state, ids,
                                                   labels)
                telemetry.step_end(tokens=B * T, flops=fpt * B * T)
            final_loss = float(loss)  # forces the whole chain
            dt = time.perf_counter() - t0
        assert jnp.isfinite(final_loss)
        tok_s = B * T * n_steps / dt
        mfu = round(tok_s * fpt / peak * 100, 1) if peak else None
        log(f"bench: transformer {tok_s:,.0f} tok/s, MFU={mfu}% on {kind} "
            f"(B={B} T={T}, {fpt / 1e9:.2f} GFLOP/token)")
        return tok_s, mfu, telemetry.ledger().summary()

    # same tokens/step at both contexts; T=8192 is the long-context
    # capability claim (flash kernels, save_flash remat) and is recorded
    # in the artifact so prose can never outrun the measurement
    tok_s, mfu, ledger = measure(8, 1024, 16)
    tok_s_long, mfu_long, _ = measure(1, 8192, 8)
    out = {"transformer_tokens_per_s": round(tok_s, 1),
           "transformer_mfu_pct": mfu,
           "transformer_tokens_per_s_long": round(tok_s_long, 1),
           "transformer_mfu_long_pct": mfu_long}
    out.update(_ledger_keys(ledger))
    return out


def _ledger_keys(summary):
    """Step-ledger summary → BENCH artifact keys (the attribution data
    regressions are diagnosed from: where did step wall time go, what
    goodput/MFU did the ledger actually account)."""
    if not summary:
        return {}
    out = {
        "step_time_p50": round(summary["step_time_p50"], 6),
        "step_time_p99": round(summary["step_time_p99"], 6),
        "step_feed_wait_fraction": round(summary["feed_wait_fraction"], 4),
        "mfu": (round(summary["mfu"], 4)
                if summary.get("mfu") is not None else None),
    }
    if summary.get("goodput_tokens_per_s") is not None:
        out["goodput_tokens_per_s"] = round(
            summary["goodput_tokens_per_s"], 1)
    if summary.get("membw_util") is not None:
        out["membw_util"] = round(summary["membw_util"], 4)
    if summary.get("bound") is not None:
        out["bound"] = summary["bound"]
    return out


def _goodput_keys(g0, g1):
    """Goodput-ledger delta over the benched window → artifact keys:
    the job-level wall-clock decomposition (goodput_fraction + named
    per-bucket badput seconds) for the same steps the step ledger
    accounted, so a perf regression shows up as a *named* badput
    bucket, not just a lower tokens/s."""
    if not g0 or not g1:
        return {}
    wall = g1["wall_s"] - g0["wall_s"]
    if wall <= 0:
        return {}
    buckets = {b: max(g1["buckets"].get(b, 0.0)
                      - g0["buckets"].get(b, 0.0), 0.0)
               for b in g1["buckets"]}
    out = {"goodput_fraction":
           round(buckets.get("productive", 0.0) / wall, 4)}
    for b, s in sorted(buckets.items()):
        if b != "productive" and s > 0.0005:
            out[f"goodput_badput_{b}_s"] = round(s, 4)
    return out


def bench_step_ledger():
    """Ledger-derived step keys on ANY backend: a small synced train
    loop through the step ledger.  When the flagship TPU transformer
    bench runs, its own ledger summary overwrites these keys — this
    keeps `step_time_*`/`goodput`/`mfu` in the artifact even on hosts
    where the flagship model cannot run."""
    import functools

    import jax
    import jax.numpy as jnp
    import optax

    sys.path.insert(0, repo_path())
    from dmlc_tpu import telemetry
    from dmlc_tpu.models import (TransformerConfig, init_params,
                                 train_step_flops, unsharded_loss)

    cfg = TransformerConfig(vocab=256, d_model=64, n_heads=2, head_dim=16,
                            d_ff=128, n_layers=2, n_experts=1,
                            dtype="float32")
    B, T, n_steps = 2, 64, 8
    params = init_params(jax.random.PRNGKey(0), cfg, n_stages=1)
    opt = optax.adamw(1e-3)
    opt_state = opt.init(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(p, s, ids, labels):
        loss, g = jax.value_and_grad(
            lambda p_: unsharded_loss(p_, ids, labels, cfg))(p)
        up, s = opt.update(g, s, p)
        return optax.apply_updates(p, up), s, loss

    ids = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab)
    labels = jnp.roll(ids, -1, axis=1)
    params, opt_state, loss = step(params, opt_state, ids, labels)
    float(loss)  # compile + settle outside the ledgered window
    telemetry.reset_steps()
    from dmlc_tpu.telemetry import goodput as goodput_mod
    gled = goodput_mod.ledger()  # opt in: step_end feeds the ledger
    g0 = gled.status()
    flops = train_step_flops(cfg, B, T)
    for _ in range(n_steps):
        telemetry.step_begin()
        params, opt_state, loss = step(params, opt_state, ids, labels)
        float(loss)  # sync per step: walls are step times, not dispatch
        telemetry.step_end(tokens=B * T, flops=flops)
    g1 = gled.status()
    summ = telemetry.ledger().summary()
    log(f"bench: step ledger p50={summ.get('step_time_p50', 0):.4f}s "
        f"p99={summ.get('step_time_p99', 0):.4f}s "
        f"goodput={summ.get('goodput_tokens_per_s', 0):,.0f} tok/s "
        f"mfu={summ.get('mfu')}")
    out = _ledger_keys(summ)
    out.update(_goodput_keys(g0, g1))
    return out


def bench_feed_to_hbm():
    """RecordIO shards → device HBM payload MB/s (BASELINE config #2).

    Measures both the padded [B, max_bytes] feed and the packed
    zero-padding feed, plus the raw device_put ceiling of this host's
    link so feed efficiency is attributable to link or pipeline."""
    import jax
    import numpy as np

    sys.path.insert(0, repo_path())
    from dmlc_tpu.feed import recordio_feed, recordio_packed_feed
    from dmlc_tpu.parallel import build_mesh

    if jax.devices()[0].platform != "tpu":
        log("bench: no TPU visible, skipping feed bench")
        return None

    # raw host→HBM ceiling at the packed feed's transfer size (6 MB,
    # matching buf_bytes below so per-transfer dispatch overhead is
    # priced into the ceiling the same way the feed pays it)
    buf = 6 << 20
    x = np.random.randint(0, 256, (buf,), dtype=np.uint8)
    dev = jax.devices()[0]
    a = jax.device_put(x, dev)
    int(np.asarray(a[0]))
    t0 = time.perf_counter()
    for _ in range(16):
        a = jax.device_put(x, dev)
    int(np.asarray(a[0]))
    ceiling = 16 * buf / 1.0e6 / (time.perf_counter() - t0)

    mesh = build_mesh(1, devices=jax.devices()[:1], dp=1, sp=1, tp=1,
                      pp=1, ep=1)

    from dmlc_tpu import metrics

    def run(make_feed, payload_of):
        best, best_steady, stalls, eff, stages = 0.0, 0.0, {}, None, {}
        for _ in range(2):
            before = metrics.snapshot().get("feed", {})
            feed = make_feed()
            t0 = time.perf_counter()
            payload = 0
            last = None
            t_warm = warm_payload = None
            for b in feed:
                payload += payload_of(b)
                last = b
                if t_warm is None:
                    # first batch landed: warmup (feed spin-up + JAX
                    # dispatch/compile) ends HERE — sync it so the
                    # steady-state clock starts from a drained pipe
                    arr = b["data"]
                    int(np.asarray(arr[(0,) * arr.ndim]))
                    t_warm = time.perf_counter()
                    warm_payload = payload
            if last is not None:
                # Index on DEVICE first — np.asarray(whole array) would
                # pull the full buffer back through the link inside dt.
                arr = last["data"]
                int(np.asarray(arr[(0,) * arr.ndim]))
            t_end = time.perf_counter()
            dt = t_end - t0
            after = metrics.snapshot().get("feed", {})
            # bytes ACTUALLY shipped over the link, from the feed's own
            # counter: cached zero shards ship nothing, and the padded
            # layout's packed transport ships offsets + payload — the
            # on-device expansion never touches the link
            shipped = (after.get("bytes_to_device", 0.0)
                       - before.get("bytes_to_device", 0.0))
            if payload / 1.0e6 / dt > best:
                best = payload / 1.0e6 / dt
                # steady state excludes the first batch and its warmup
                if t_warm is not None and payload > warm_payload:
                    best_steady = ((payload - warm_payload) / 1.0e6
                                   / (t_end - t_warm))
                eff = payload / shipped if shipped else None
                # producer stall = waiting on a full queue (consumer is
                # the bottleneck); consumer stall = waiting on an empty
                # one (host pipeline / link is) — overlap attribution
                stalls = {
                    k: round(after.get(f"{k}_secs", 0.0)
                             - before.get(f"{k}_secs", 0.0), 3)
                    for k in ("producer_stall", "consumer_stall")}
                # producer-side stage split: parse_native = the fused
                # scan+verify (+ fused libsvm tokenize), pack = batch
                # assembly (pad-pack / pack_spans), crc = residual
                # integrity work OUTSIDE the fused scan (reject and
                # skip-list routing; ≈ 0 proves single-pass integrity)
                stages = {
                    k: round(after.get(f"{k}_secs", 0.0)
                             - before.get(f"{k}_secs", 0.0), 3)
                    for k in ("parse_native", "pack", "crc")}
        return best, best_steady, stalls, eff, stages

    # padded contract, packed transport: records stage back-to-back in a
    # 6 MB buffer per batch and a jitted on-device gather materializes
    # the [B, max_bytes] padded layout AFTER the link, so the padded
    # path ships payload (not padding) and tracks the same ceiling as
    # the packed layout
    padded, padded_steady, padded_stalls, padded_eff, padded_stages = run(
        lambda: recordio_feed(DATA, mesh, batch_records=256,
                              max_bytes=96 << 10, pack_bytes=buf),
        lambda b: int(np.sum(np.asarray(b["length"]))))
    # 6 MB batches: small enough that the epoch-tail partial batch costs
    # < 5% shipped efficiency (24 MB batches left 11% on the table),
    # large enough that per-transfer dispatch overhead stays invisible
    # next to a ~0.2 s transfer on this link
    packed, packed_steady, packed_stalls, packed_eff, packed_stages = run(
        lambda: recordio_packed_feed(DATA, mesh, buf_bytes=buf,
                                     max_records=1024),
        lambda b: int(np.asarray(b["offsets"])[int(np.asarray(b["count"])[0])]))
    # Payload ÷ shipped bytes: what each layout costs the host link,
    # counted in bytes so it does not depend on the link's speed.
    log(f"bench: feed→HBM padded={padded:.1f} (steady {padded_steady:.1f}) "
        f"packed={packed:.1f} (steady {packed_steady:.1f}) "
        f"device_put ceiling={ceiling:.1f} MB/s "
        f"(shipped-eff padded={padded_eff:.2f} packed={packed_eff:.2f}; "
        f"stalls: padded={padded_stalls} packed={packed_stalls}; "
        f"stages: padded={padded_stages} packed={packed_stages})")
    return {"recordio_feed_to_hbm_MBps": round(packed, 1),
            "recordio_feed_to_hbm_MBps_steady": round(packed_steady, 1),
            "recordio_feed_padded_MBps": round(padded, 1),
            "recordio_feed_padded_MBps_steady": round(padded_steady, 1),
            "device_put_ceiling_MBps": round(ceiling, 1),
            "feed_packed_shipped_efficiency": round(packed_eff, 3),
            "feed_padded_shipped_efficiency": round(padded_eff, 3),
            "feed_padded_producer_stall_s":
                padded_stalls.get("producer_stall"),
            "feed_padded_consumer_stall_s":
                padded_stalls.get("consumer_stall"),
            "feed_packed_producer_stall_s":
                packed_stalls.get("producer_stall"),
            "feed_packed_consumer_stall_s":
                packed_stalls.get("consumer_stall"),
            "feed_padded_parse_native_s":
                padded_stages.get("parse_native"),
            "feed_padded_pack_s": padded_stages.get("pack"),
            "feed_padded_crc_s": padded_stages.get("crc"),
            "feed_packed_parse_native_s":
                packed_stages.get("parse_native"),
            "feed_packed_pack_s": packed_stages.get("pack"),
            "feed_packed_crc_s": packed_stages.get("crc")}


def main():
    os.makedirs(WORK, exist_ok=True)
    sys.path.insert(0, repo_path())
    from dmlc_tpu.compile_cache import place_compile_cache

    place_compile_cache()
    ensure_data()
    ours = run_ours()
    extra = {}
    baseline = None
    idx_vs = None
    if ensure_refbin():
        baseline = run_reference()
        log(f"bench: ours={ours:.1f} MB/s reference={baseline:.1f} MB/s")
        try:
            ours_idx = run_ours_indexed_shuffled()
            ref_idx = run_reference(indexed=True)
            extra["indexed_shuffled_read_MBps"] = round(ours_idx, 1)
            idx_vs = round(ours_idx / ref_idx, 3) if ref_idx else None
            extra["indexed_shuffled_vs_baseline"] = idx_vs
            log(f"bench: indexed-shuffled ours={ours_idx:.1f} "
                f"reference={ref_idx:.1f} MB/s")
        except Exception as e:  # noqa: BLE001
            log(f"bench: indexed bench failed: {e!r}")
    # step-ledger fallback first: the flagship transformer bench, when
    # it runs (TPU), overwrites the ledger keys with flagship numbers
    for fn in (bench_step_ledger, bench_transformer, bench_feed_to_hbm):
        try:
            r = fn()
            if r:
                extra.update(r)
        except Exception as e:  # noqa: BLE001
            log(f"bench: {fn.__name__} failed: {e!r}")
    # compile-ledger keys across every bench above: a perf PR that adds
    # a recompile per step shows up here before it shows up in step time
    try:
        from dmlc_tpu.telemetry import compute

        if compute.enabled():
            extra["recompiles"] = compute.recompiles_total()
            extra["hbm_peak_bytes"] = compute.sample_hbm(
                publish=False).get("peak_bytes")
    except Exception as e:  # noqa: BLE001
        log(f"bench: compute ledger snapshot failed: {e!r}")
    result = {
        "metric": "recordio_inputsplit_read_MBps",
        "value": round(ours, 1),
        "unit": "MB/s",
        "vs_baseline": round(ours / baseline, 3) if baseline else None,
        "extra_metrics": extra,
    }
    # structured telemetry snapshot (histogram percentiles, span count)
    # accumulated across every bench above — the attribution data later
    # perf PRs cite
    try:
        from dmlc_tpu import telemetry

        result["telemetry"] = telemetry.export_json()
    except Exception as e:  # noqa: BLE001
        log(f"bench: telemetry snapshot failed: {e!r}")
    print(json.dumps(result))


if __name__ == "__main__":
    main()
