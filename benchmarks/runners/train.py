"""Runner of ``kind: train`` cells: recordio_feed -> make_train_step,
the call pattern of chip_smoke.py and examples/train_lm_recordio.py."""

from __future__ import annotations

import math
import os
import time

from benchmarks import harness, traffic
from benchmarks.harness import annotate, log, need


def _write_records(cell, seed, vocab):
    """The seeded token records, written once per checkout and seed."""
    from dmlc_tpu.io.recordio import RecordIOWriter
    from dmlc_tpu.io.stream import Stream

    t = cell.traffic
    tag = f"{cell.traffic_name}-B{t['B']}-T{t['T']}-V{vocab}-seed{seed}"
    path = os.path.join(harness.HERE, "out", "records", tag + ".rec")
    if not os.path.exists(path):
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp"
        with Stream.create(tmp, "w") as s:
            writer = RecordIOWriter(s)
            for payload in traffic.token_records(cell.traffic_name, t,
                                                 seed, vocab):
                writer.write_record(payload)
        os.replace(tmp, path)
    return path


def _batches(feed, t):
    """(ids, labels) forever: epochs follow each other, and the epoch's
    zero-padded tail batch is skipped."""
    import jax
    import numpy as np

    while True:
        for batch in feed:
            if np.any(np.asarray(batch["length"]) == 0):
                continue
            toks = jax.lax.bitcast_convert_type(
                batch["data"].reshape(-1, t + 1, 4), jax.numpy.int32)
            yield toks[:, :-1], toks[:, 1:]


class _Loop:
    """The measured loop: take a batch, dispatch a step, read the loss
    every ``read_every`` steps as a trainer that logs does."""

    def __init__(self, step, batches, read_every, state):
        self.step, self.batches, self.read_every = step, batches, read_every
        # the loop alone holds the (params, opt_state) of the moment: a
        # second reference anywhere keeps a superseded 6 GB state alive,
        # and the undonated step has no room for one
        self.state = state
        self.losses = []  # device scalars, fetched after the window
        self.steps = 0
        self.feed_wait_s = 0.0

    def run(self, seconds):
        """Dispatch steps until the clock passes ``seconds``, then block
        on the last one.  Returns (elapsed seconds, steps)."""
        import jax

        (params, opt_state), self.state = self.state, None
        jax.block_until_ready(params)
        t_open = time.monotonic()
        first = self.steps
        while True:
            t0 = time.monotonic()
            with annotate("bench.feed_next"):
                ids, labels = next(self.batches)
            self.feed_wait_s += time.monotonic() - t0
            with annotate("bench.step_dispatch"):
                params, opt_state, loss = self.step(params, opt_state,
                                                    ids, labels)
            self.losses.append(loss)
            self.steps += 1
            if self.steps % self.read_every == 0:
                with annotate("bench.sync"):
                    float(loss)  # the trainer's log line
            if time.monotonic() - t_open >= seconds:
                break
        with annotate("bench.sync"):
            jax.block_until_ready((params, opt_state, loss))
        elapsed = time.monotonic() - t_open
        self.state = (params, opt_state)
        return elapsed, self.steps - first


def run(cell, args, t_start):
    import jax
    import numpy as np
    import optax
    from jax.sharding import NamedSharding

    device = harness.claim_device(cell, args.rehearse)
    compiles = harness.CompileCounter()

    from dmlc_tpu import native
    from dmlc_tpu.feed import recordio_feed
    from dmlc_tpu.models import transformer as tfm
    from dmlc_tpu.parallel import build_mesh

    need(native.available(), "the native parsers did not build (g++?)")
    cfg = tfm.TransformerConfig(**cell.config["model"])
    axes = cell.config["mesh"]
    mesh = build_mesh(cell.chips, **axes)
    b, t = cell.traffic["B"], cell.traffic["T"]
    n_parts = axes["dp"] * axes["sp"]
    need(b % n_parts == 0, f"B={b} does not divide over dp x sp={n_parts}")
    opt = cell.config["train"]["optimizer"]
    need(opt["name"] == "adamw" and not cell.config["train"]["donate"],
         "this runner drives make_train_step as the program has it: "
         "AdamW, nothing donated")

    # weights on the device, in the type they are trained in, placed as
    # the step wants them, in one jitted call from the seed
    shardings = jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                             tfm.param_specs())
    params = jax.jit(lambda key: tfm.init_params(key, cfg, axes["pp"]),
                     out_shardings=shardings)(jax.random.PRNGKey(args.seed))
    step, init_state = tfm.make_train_step(
        mesh, cfg, optimizer=optax.adamw(opt["learning_rate"]))
    jax.block_until_ready(params)
    log("weights made")

    path = _write_records(cell, args.seed, cfg.vocab)
    feed = recordio_feed(path, mesh, batch_records=b // n_parts,
                         max_bytes=(t + 1) * 4)
    try:
        batches = _batches(feed, t)
        check = cell.config["correct"]
        first = [next(batches) for _ in range(check["loss_batches"])]
        log(f"records written, {len(first)} batch(es) on the device")
        # the reference's loss on each of them at the seeded weights,
        # before the optimizer state takes its room.  One chip computes
        # it: the reference knows nothing of meshes
        reference = harness.reference_for(cell.config)
        one = jax.devices()[0]
        params_one = jax.device_put(params, one)
        ref_losses = [float(reference.mean_loss(params_one, *(
            jax.device_put(np.asarray(x), one) for x in pair)))
            for pair in first]
        del params_one
        log("reference losses at the seeded weights: "
            + ", ".join(f"{x:.6f}" for x in ref_losses))

        # warm-up: the first step compiles (or loads) the one program
        # this cell uses.  Each of those batches goes through the step
        # at the seeded weights, one step at a time: a step's new state
        # is 6 GB, and the undonated step has no room for a second
        state = (params, jax.jit(init_state)(params))
        seeded_losses = []
        for i, (ids, labels) in enumerate(first):
            out = step(*state, ids, labels)
            seeded_losses.append(float(out[2]))
            if i < len(first) - 1:
                del out
        loss0 = seeded_losses[0]
        log("the step's losses at the seeded weights: "
            + ", ".join(f"{x:.6f}" for x in seeded_losses))
        loop = _Loop(step, batches, cell.traffic["loss_read_every"],
                     out[:2])
        del params, state, out, first, ids, labels
        for _ in range(cell.traffic["warmup_steps"] - 1):
            loop.run(0.0)  # one step, then a sync
        warm = len(loop.losses)

        before = harness.program_state()
        compiles_open = compiles.count
        setup_s = time.monotonic() - t_start
        log(f"set-up {setup_s:.2f}s ({compiles.count} programs, "
            f"{compiles.misses} of them not in the compile cache); window "
            f"of {args.seconds}s opens")
        loop.feed_wait_s = 0.0
        window_s, steps = loop.run(args.seconds)
        after = harness.program_state()
        compiles_in_window = compiles.count - compiles_open
        tok_s = steps * b * t / window_s
        log(f"window: {steps} steps in {window_s:.3f}s = {tok_s:.1f} tok/s; "
            f"the host spent {100 * loop.feed_wait_s / window_s:.1f}% of it "
            "inside next(feed) (with steps running ahead that is where it "
            "blocks on the device; feed_wait_share is the device's wait)")
        facts = {"window_s": window_s, "steps": steps, "train_tok_s": tok_s}

        reduction = None
        if args.trace:
            trace_dir = os.path.join(cell.out_dir, "trace")
            with harness.traced(trace_dir) as found:
                traced_s, traced_steps = loop.run(
                    cell.traffic["trace_seconds"])
            traced_tok_s = traced_steps * b * t / traced_s
            log(f"traced window: {traced_steps} steps in {traced_s:.3f}s = "
                f"{traced_tok_s:.1f} tok/s, "
                f"{100 * (traced_tok_s / tok_s - 1):+.2f}% against the "
                f"untraced window; trace at {found['path']}")
            need(found["path"], "the profiler wrote no trace")
            reduction = harness.reduce_traced_window(
                found["path"], device["platform"])
            facts["trace_steps"] = traced_steps
            facts["traced_tok_s"] = traced_tok_s
    finally:
        feed.close()

    losses = [float(x) for x in jax.device_get(loop.losses)]
    window_losses = losses[warm:]
    nonfinite = sum(not math.isfinite(x) for x in window_losses)
    logged = window_losses[loop.read_every - 1::loop.read_every][-5:] \
        or window_losses[-5:]
    learned = sum(logged) / len(logged) < loss0
    # root mean square over the batches: one batch's mean loss averages
    # rounding away, and its difference can fall near zero by chance
    diffs = [got - ref for got, ref in zip(seeded_losses, ref_losses)]
    rms = math.sqrt(sum(d * d for d in diffs) / len(diffs))
    close = rms <= check["loss_tolerance"]
    log("correct: step - reference at the seeded weights "
        + ", ".join(f"{d:+.6f}" for d in diffs)
        + f", rms {rms:.6f} <= {check['loss_tolerance']}: {close}; mean of "
        f"the last logged losses {sum(logged) / len(logged):.4f} < first: "
        f"{learned}; non-finite losses in the window: {nonfinite}")
    harness.check_program_health(after, args.rehearse)
    obs = harness.observations(cell, device, facts, before, after,
                               compiles_in_window, reduction)
    end_to_end = {"train_tok_s": tok_s, "setup_s": setup_s}
    return {"device": device, "correct": close and learned
            and nonfinite == 0, "attempted": steps, "failed": nonfinite,
            "end_to_end": end_to_end, "obs": obs, "reduction": reduction}
