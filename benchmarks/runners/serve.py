"""Runner of ``kind: serve`` cells: InferenceEngine + ServingHTTPServer
built in this process exactly as bin/dmlc-serve builds them (the
process that holds the chip is the one that can trace it), driven over
HTTP by benchmarks/client.py, a child process that never imports jax."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import urllib.request

from benchmarks import costs, harness, measure, traffic
from benchmarks.harness import annotate, log, need

REQUEST_TIMEOUT_S = 300.0


def _post(url, prompt, max_tokens):
    req = urllib.request.Request(
        url + "/generate",
        data=json.dumps({"prompt": prompt,
                         "max_tokens": max_tokens}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=REQUEST_TIMEOUT_S) as resp:
        doc = json.loads(resp.read())
    need(not doc.get("error") and doc["n_generated"] == max_tokens,
         f"a request sent alone failed: {doc}")
    return doc


def _sleep_until(t):
    delay = t - time.monotonic()
    if delay > 0:
        time.sleep(delay)


def _check_against_reference(cell, params, checked, vocab, seed):
    """Teacher-forced: the reference's full forward over prompt + the
    engine's own output must put every token the engine emitted within
    the margin of the reference's top logit at its position.  The same
    check on a copy with one emitted token replaced by a seeded random
    id (the control) must fail."""
    import numpy as np

    reference = harness.reference_for(cell.config)
    margin = cell.config["correct"]["logit_margin"]
    _, out_max = traffic.dist_bounds(cell.traffic["output"])
    worst, control_caught = 0.0, 0
    for rec in checked:
        req = traffic.request(cell.traffic_name, cell.traffic, seed, vocab,
                              rec["client"], rec["index"])
        out = rec["output_ids"]
        ids = req["prompt"] + out[:-1]
        # right-padding changes nothing before it under a causal mask;
        # whole blocks keep the reference to a few shapes per mix
        padded = -(-len(ids) // reference.Q_BLOCK) * reference.Q_BLOCK
        at = np.arange(len(req["prompt"]) - 1, len(ids))
        logits = np.asarray(reference.logits_at(
            params, np.asarray(ids + [0] * (padded - len(ids)), np.int32),
            np.pad(at, (0, out_max - len(at)), mode="edge")))[:len(at)]
        top = logits.max(axis=-1)
        gaps = top - logits[np.arange(len(out)), out]
        worst = max(worst, float(gaps.max()))
        rng = np.random.default_rng([seed, rec["client"], rec["index"]])
        pos = int(rng.integers(len(out)))
        # another id than the one emitted there
        wrong = int(out[pos] + 1 + rng.integers(vocab - 1)) % vocab
        control_caught += bool(top[pos] - logits[pos, wrong] > margin)
    n = len(checked)
    log(f"reference check over {n} requests: the engine's tokens are "
        f"within {worst:.4f} of the reference's top logit (margin "
        f"{margin}); the control failed in {control_caught} of {n}")
    return n > 0 and worst <= margin and control_caught == n


def run(cell, args, t_start):
    import jax

    sv = cell.config["serve"]
    os.environ.update(sv["env"])
    device = harness.claim_device(cell, args.rehearse)
    compiles = harness.CompileCounter()

    from dmlc_tpu.models import transformer as tfm
    from dmlc_tpu.serving import InferenceEngine, ServingHTTPServer

    cfg = tfm.TransformerConfig(**cell.config["model"])
    mix = cell.traffic
    # weights on the device in the type they are served in, in one
    # jitted call from the seed
    params = jax.jit(lambda key: tfm.init_params(key, cfg))(
        jax.random.PRNGKey(args.seed))
    engine = InferenceEngine(
        params, cfg, n_blocks=sv["n_blocks"], block_size=sv["block_size"],
        max_active=sv["max_active"], queue_depth=sv["queue_depth"])
    engine.start()
    server = ServingHTTPServer(engine, host="127.0.0.1", port=0)
    jax.block_until_ready(params)
    log("weights made, engine and server up")
    child = None
    try:
        # warm-up: each request alone, so that its own widths decide the
        # programs it visits
        plan = traffic.warmup_requests(mix, cfg.vocab, sv["block_size"])
        alone = []
        for w in plan:
            alone.append(_post(server.url, w["prompt"], w["max_tokens"]))
            log(f"warm-up: {len(w['prompt'])} prompt tokens, "
                f"{w['max_tokens']} generated, alone; "
                f"{compiles.count} programs so far")
        warmed = harness.program_state()
        want = traffic.decode_widths(mix, sv["block_size"])
        got = warmed["sites"]["serving.decode_paged"]["signatures"]
        need(got >= len(want), f"warm-up visited {got} decode programs, "
             f"the mix can reach widths {sorted(want)}")
        log(f"warm-up: {len(plan)} requests alone, {got} decode and "
            f"{warmed['sites']['serving.prefill']['signatures']} prefill "
            f"programs; {compiles.count} programs so far, "
            f"{compiles.misses} of them not in the compile cache")

        # the clients start a ramp before the window, so that it opens
        # on a full engine
        trace_s = mix["trace_seconds"] if args.trace else 0.0
        # the profiler takes a moment to start; the clients outlast it
        t_extra = trace_s + 5.0 if args.trace else 0.0
        os.makedirs(cell.out_dir, exist_ok=True)
        spec_path = os.path.join(cell.out_dir, "client_spec.json")
        out_path = os.path.join(cell.out_dir, "client_records.json")
        if os.path.exists(out_path):
            os.unlink(out_path)
        t_clients = time.monotonic() + 0.5
        t_open = t_clients + mix["ramp_seconds"]
        t_close = t_open + args.seconds
        with open(spec_path, "w") as f:
            json.dump({"url": server.url, "mix_name": cell.traffic_name,
                       "mix": mix, "seed": args.seed, "vocab": cfg.vocab,
                       "t_start": t_clients, "t_open": t_open,
                       "t_close": t_close, "t_stop": t_close + t_extra,
                       "timeout_s": REQUEST_TIMEOUT_S, "out": out_path}, f)
        child = subprocess.Popen(
            [sys.executable, os.path.join(harness.HERE, "client.py"),
             spec_path], cwd=harness.ROOT)
        _sleep_until(t_open)
        before = harness.program_state()
        compiles_open = compiles.count
        setup_s = t_open - t_start
        log(f"set-up {setup_s:.2f}s; window of {args.seconds}s opens")
        # once a second: the cache's own count of blocks in use
        pool, cpu_open = [], time.process_time()
        with annotate("bench.client_wait"):
            while time.monotonic() < t_close:
                pool.append(engine.cache.stats()["blocks_in_use"])
                _sleep_until(min(t_close, time.monotonic() + 1.0))
        after = harness.program_state()
        # this process's CPU seconds, logged to set a slow run beside
        # (PERF.md section 7): a host that was taken away shows here
        host_cpu_s = time.process_time() - cpu_open
        compiles_in_window = compiles.count - compiles_open

        traced_window = None
        if args.trace:
            # after the window, with the clients still running
            with harness.traced(os.path.join(cell.out_dir, "trace")) \
                    as found:
                t_traced = time.monotonic()
                with annotate("bench.client_wait"):
                    time.sleep(trace_s)
                traced_window = (t_traced, time.monotonic())
            need(found["path"], "the profiler wrote no trace")

        # the clients let their requests in flight finish, then report
        rc = child.wait(timeout=REQUEST_TIMEOUT_S + 60)
        need(rc == 0, f"the client process exited {rc}")
        with open(out_path) as f:
            records = json.load(f)
        reduction = harness.reduce_traced_window(
            found["path"], device["platform"]) if args.trace else None

        # alone again, a warm-up request returns the same ids: the
        # shortest prompt that generated a fair number of tokens
        i = min(range(len(plan)), key=lambda i: (
            plan[i]["max_tokens"] < 16, len(plan[i]["prompt"])))
        again = _post(server.url, plan[i]["prompt"], plan[i]["max_tokens"])
        same = again["output_ids"] == alone[i]["output_ids"]
        log(f"the same request sent twice alone returned the same ids: "
            f"{same}")
        checked = [r for r in records if r.get("output_ids")]
        agrees = _check_against_reference(cell, params, checked, cfg.vocab,
                                          args.seed)
        final = harness.program_state()
    finally:
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        server.close()
        engine.close()

    facts = measure.serve_window(records, t_open, t_close)
    facts["host_cpu_s"] = host_cpu_s
    facts["pool_blocks"] = sv["n_blocks"]
    facts["pool_blocks_in_use"] = sum(pool) / len(pool)
    facts["pool_blocks_in_use_max"] = max(pool)
    answered = [r for r in measure.in_window(records, t_open, t_close)
                if not measure.request_failed(r)]
    steps = measure.delta(after["counters"], before["counters"]).get(
        "counters.serving.paged_decode_steps")
    if steps:
        facts["ctx_tokens_per_decode_step"] = sum(
            costs.context_tokens_read(r["n_prompt"], r["n_generated"])
            for r in answered) / steps
    if args.trace:
        traced = measure.serve_window(records, *traced_window)
        log(f"traced window: {traced['serve_tok_s']:.1f} tok/s, "
            f"{100 * (traced['serve_tok_s'] / facts['serve_tok_s'] - 1):+.2f}%"
            " against the untraced window")
        facts["traced_tok_s"] = traced["serve_tok_s"]
    log(f"window: {json.dumps(facts)}")
    harness.check_program_health(final, args.rehearse)
    need(facts["preemptions"] == 0, "requests were preempted and "
         "recomputed: the pool is too small for this mix")
    obs = harness.observations(cell, device, facts, before, after,
                               compiles_in_window, reduction,
                               responses=answered)
    end_to_end = {"serve_tok_s": facts["serve_tok_s"],
                  "norm_lat_p90": facts["norm_lat_p90"],
                  "setup_s": setup_s}
    return {"device": device, "correct": bool(same and agrees),
            "attempted": facts["attempted"], "failed": facts["failed"],
            "end_to_end": end_to_end, "obs": obs, "reduction": reduction}
