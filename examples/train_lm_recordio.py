"""Train the transformer LM from RecordIO token shards — the full TPU
spine in one script (BASELINE configs #2/#5 shape): InputSplit →
device feed → sharded model → checkpoint/resume → metrics.

  python examples/train_lm_recordio.py <shards.rec> [steps] [ckpt_dir]

With a checkpoint dir the run resumes from the latest step-numbered
checkpoint (CheckpointManager over the Stream/URI layer, so the same
path works with gs://) and saves every 20 steps.

Each RecordIO record holds a fixed-length sequence of int32 token ids.
The packed device feed streams records into HBM; the model trains with
whatever mesh the local devices support (1 chip → trivial mesh; under a
multi-chip runtime the same code shards over dp).  Run
`python examples/train_lm_recordio.py --make-data out.rec` first to
generate a synthetic shard.

Elastic mode (DMLC_ELASTIC=1 under an elastic tracker, ckpt_dir
required): each process joins the tracker world, partitions data by
(rank, world) through the byte-range contract, averages gradients over
the host collective, and SURVIVES the world resizing mid-run — a
collective interrupted by a preempted peer raises WorldResized; the
loop re-enters rendezvous (possibly under a new rank), repartitions the
feed in place, restores params+optimizer state from the last COMMITTED
checkpoint onto the mesh, and keeps training without a process restart.

Self-healing (resilience.selfheal): every step's loss and gradient
norm pass through a SelfHealGuard — a non-finite or EWMA-spiking step
is SKIPPED (jax arrays are immutable, so reverting to the pre-step
(params, opt_state) references is free); DMLC_SELFHEAL_MAX_SKIPS
consecutive skips trigger a ROLLBACK-AND-REPLAY to the last committed
checkpoint (the WorldResized recovery path's restore/resync machinery,
reused) with integrity-quarantined spans skip-listed out of the replay;
exhausted rollbacks ABORT with a postmortem naming the suspect spans.
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

SEQ = 128
VOCAB = 512
DTYPE = "float32"  # this 256-wide demo model's, whatever it runs on


def make_data(path, n_records=2048, seed=0):
    from dmlc_tpu.io.recordio import RecordIOWriter
    from dmlc_tpu.io.stream import Stream

    rng = np.random.default_rng(seed)
    with Stream.create(path, "w") as s:
        w = RecordIOWriter(s)
        for _ in range(n_records):
            # a learnable distribution: arithmetic sequences mod VOCAB.
            # SEQ+1 tokens per record so ids/labels split without the
            # wrap-around garbage target a plain roll would create
            start, step = rng.integers(0, VOCAB), rng.integers(1, 7)
            ids = (start + step * np.arange(SEQ + 1)) % VOCAB
            w.write_record(ids.astype(np.int32).tobytes())
    print(f"wrote {n_records} records to {path}")


def _elastic_enabled() -> bool:
    from dmlc_tpu.base import get_env

    return get_env("DMLC_ELASTIC", False) \
        and bool(os.environ.get("DMLC_TRACKER_URI"))


class _ElasticTrainer:
    """The elastic half of the loop: tracker membership, host-collective
    gradient averaging, and the WorldResized recovery protocol."""

    def __init__(self, manager, mesh):
        from dmlc_tpu.base import get_env
        from dmlc_tpu.parallel.overlap import GradientBucketer
        from dmlc_tpu.telemetry import HeartbeatSender
        from dmlc_tpu.tracker.client import TrackerClient

        self.client = TrackerClient().start()
        self.hb = HeartbeatSender(self.client, interval=1.0)
        self.manager = manager
        self.mesh = mesh
        # overlapped gradient reduction (DMLC_COLL_OVERLAP=0 opts out):
        # buckets allreduce on a background thread while later leaves
        # are still being fetched off-device and packed; a WorldResized
        # raised on that thread transports through the bucket futures
        # and re-raises at the join, inside the existing recovery path
        # in-place (out=a) on the bucket buffers the bucketer owns: the
        # steady-state gradient exchange allocates nothing per bucket
        self.bucketer = (
            GradientBucketer(lambda a: self.client.allreduce_sum(a, out=a))
            if get_env("DMLC_COLL_OVERLAP", True) else None)

    @property
    def world(self):
        return (self.client.rank, self.client.world_size)

    @staticmethod
    def _flatten(tree):
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(tree)
        flat = np.concatenate(
            [np.asarray(v, np.float64).ravel() for v in leaves])
        return leaves, treedef, flat

    @staticmethod
    def _unflatten(leaves, treedef, flat):
        import jax

        out, pos = [], 0
        for v in leaves:
            n = int(np.size(v))
            out.append(flat[pos: pos + n].reshape(np.shape(v)).astype(
                np.asarray(v).dtype))
            pos += n
        return jax.tree_util.tree_unflatten(treedef, out)

    def allreduce_grads(self, grads, loss: float):
        """Average gradients (and the loss) over the elastic world via
        the host collective; raises WorldResized on membership change.
        Also returns the global grad norm (computed on the AVERAGED
        gradients, so every rank reaches the same self-heal verdict)."""
        if self.bucketer is not None:
            return self._allreduce_grads_overlapped(grads, loss)
        leaves, treedef, flat = self._flatten(grads)
        flat = np.concatenate([flat.astype(np.float32),
                               np.asarray([loss], np.float32)])
        total = self.client.allreduce_sum(flat)
        total /= float(self.client.world_size)
        gnorm = float(np.sqrt(np.sum(np.square(total[:-1]),
                                     dtype=np.float64)))
        return (self._unflatten(leaves, treedef, total[:-1]),
                float(total[-1]), gnorm)

    def _allreduce_grads_overlapped(self, grads, loss: float):
        """Bucketed-overlapped version of ``allreduce_grads``: leaves
        are packed reverse-topologically into DMLC_COLL_BUCKET_MB
        buckets, each bucket's allreduce runs on the bucketer's
        background thread while later leaves are still converted and
        packed, and the join re-raises any collective-thread exception
        (incl. WorldResized) here.  All-or-nothing: on failure the
        input gradients are untouched."""
        import jax

        w = float(self.client.world_size)
        red_loss, red = self.bucketer.reduce_tree(
            (np.asarray([loss], np.float32), grads))
        gnorm = float(np.sqrt(sum(
            float(np.sum(np.square(np.asarray(r, np.float64) / w)))
            for r in jax.tree_util.tree_leaves(red))))
        avg = jax.tree_util.tree_map(
            lambda r, g: (r / w).astype(np.asarray(g).dtype), red, grads)
        return avg, float(red_loss[0]) / w, gnorm

    def _broadcast_state(self, params, opt_state, done: int):
        """Make rank 0's (params, opt_state, step) authoritative
        everywhere — the shared tail of resync and rollback.  Rank 0
        restores the last COMMITTED checkpoint when one exists (its own
        memory otherwise) and broadcasts to the world."""
        if self.client.rank == 0:
            step, restored = self.manager.restore_latest(
                {"params": params, "opt": opt_state}, mesh=self.mesh)
            if step is not None:
                params, opt_state, done = (restored["params"],
                                           restored["opt"], step)
        leaves, treedef, flat = self._flatten((params, opt_state))
        if self.client.rank != 0:
            flat = np.zeros_like(flat)  # shapes/dtypes are uniform
        flat = self.client.broadcast(
            np.concatenate([flat, [float(done)]]), root=0)
        params, opt_state = self._unflatten(leaves, treedef, flat[:-1])
        return params, opt_state, int(flat[-1])

    def resync(self, feed, params, opt_state, done: int):
        """WorldResized recovery: re-enter rendezvous, repartition the
        feed, then make rank 0's state authoritative everywhere.

        The interrupted step's allreduce may have completed on some
        ranks and not others, so replicas are one step apart until the
        broadcast realigns them.  May itself raise WorldResized
        (another resize mid-recovery); callers loop."""
        self.client.resize()
        feed.resize(self.world)
        params, opt_state, done = self._broadcast_state(
            params, opt_state, done)
        print(f"resized into rank {self.client.rank}/"
              f"{self.client.world_size} (gen {self.client.gen}); "
              f"resynced at step {done}", flush=True)
        return params, opt_state, done

    def rollback(self, feed, params, opt_state, done: int):
        """Self-heal rollback-and-replay: same restore/broadcast
        machinery as resync, but membership is unchanged — only the
        state rolls back (and the data stream restarts; quarantined
        spans are skip-listed out by the readers).  The guard's verdict
        is deterministic on the allreduced loss, so every rank calls
        this on the same step without coordination."""
        feed.close()  # abandon the in-flight epoch before re-iterating
        params, opt_state, done = self._broadcast_state(
            params, opt_state, done)
        print(f"selfheal: rolled back to committed step {done} "
              f"(rank {self.client.rank})", flush=True)
        return params, opt_state, done

    def close(self):
        if self.bucketer is not None:
            self.bucketer.close()
        self.hb.close()
        self.client.shutdown()


def main():
    if len(sys.argv) < 2:
        print("usage: train_lm_recordio.py (<shards.rec> [steps] "
              "[ckpt_dir] | --make-data <out.rec>)", file=sys.stderr)
        sys.exit(2)
    if sys.argv[1] == "--make-data":
        make_data(sys.argv[2])
        return
    uri = sys.argv[1]
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 30
    ckpt_dir = sys.argv[3] if len(sys.argv) > 3 else None

    import jax
    import jax.numpy as jnp
    import optax

    from dmlc_tpu import metrics
    from dmlc_tpu.compile_cache import place_compile_cache
    from dmlc_tpu.feed import recordio_feed
    from dmlc_tpu.models import (TransformerConfig, init_params,
                                 make_train_step, unsharded_loss)
    from dmlc_tpu.parallel import build_mesh
    from dmlc_tpu.parallel.collectives import initialize_distributed
    from dmlc_tpu.tracker.client import WorldResized

    place_compile_cache()
    elastic = _elastic_enabled()
    if not elastic:
        # under dmlc-submit with world > 1 this joins every launched
        # process into one jax.distributed job (coordinator allocated by
        # the tracker, DMLC_JAX_COORD_URI/PORT) so jax.devices() below
        # spans the whole pod; no-op single-process.  Elastic mode keeps
        # processes independent instead — jax.distributed gangs cannot
        # resize, the host collective can.
        initialize_distributed()

    n_dev = len(jax.devices())
    mesh = build_mesh(n_dev, dp=n_dev, sp=1, tp=1, pp=1, ep=1)
    cfg = TransformerConfig(
        vocab=VOCAB, d_model=256, n_heads=4, head_dim=64, d_ff=512,
        n_layers=4, n_experts=1, microbatches=1, dtype=DTYPE, remat=True)
    params = init_params(jax.random.PRNGKey(0), cfg, n_stages=1)
    optimizer = optax.adamw(3e-4)
    if not elastic:
        # ledger=False: this loop drives the step ledger ITSELF so the
        # batch fetch lands inside the step window — feed.wait is then
        # billed to the step's feed-wait share (make_train_step's
        # built-in ledger would only see the compute half).
        # grad_norm=True: the self-heal guard checks the global grad
        # norm each step, catching NaNs before the loss shows them
        step, init_state = make_train_step(
            mesh, cfg, optimizer=optimizer, ledger=False, grad_norm=True)
        opt_state = init_state(params)
    else:
        # elastic mode shards nothing across processes at the XLA layer
        # (a jax.distributed gang cannot resize); every process holds a
        # full replica and the host collective averages gradients
        opt_state = optimizer.init(params)

    def _restore_with_stream(mgr, tmpl, mesh, with_stream=True):
        """restore_latest including the persisted stream position (the
        count of quality batches consumed when the checkpoint
        committed); pre-PR checkpoints lack the leaf and restore with
        position unknown.  ``with_stream=False`` skips the probe —
        elastic checkpoints never carry the leaf, and probing would
        fully restore every shard before the miss is detected (2x
        checkpoint read I/O on every elastic resume)."""
        from dmlc_tpu.checkpoint import MissingLeaf

        if not with_stream:
            step, restored = mgr.restore_latest(dict(tmpl), mesh=mesh)
            return step, restored, None
        try:
            step, restored = mgr.restore_latest(
                dict(tmpl, stream=np.zeros(1, np.int64)), mesh=mesh)
        except MissingLeaf:
            step, restored = mgr.restore_latest(dict(tmpl), mesh=mesh)
            return step, restored, None
        if step is None:
            return None, None, None
        return step, restored, int(np.asarray(restored["stream"])[0])

    manager = start_at = stream_resume = None
    if ckpt_dir:
        from dmlc_tpu.checkpoint import CheckpointManager

        manager = CheckpointManager(ckpt_dir, max_to_keep=2)
        # faithful resume: params AND optimizer moments/step count travel
        # together (restoring params alone would reset AdamW's state)
        start_at, restored, stream_resume = _restore_with_stream(
            manager, {"params": params, "opt": opt_state}, mesh,
            with_stream=not elastic)
        if start_at is not None:
            params, opt_state = restored["params"], restored["opt"]
            print(f"resumed from step {start_at}", flush=True)

    trainer = None
    if elastic:
        assert manager is not None, \
            "elastic mode needs a checkpoint dir (resize restores from it)"
        trainer = _ElasticTrainer(manager, mesh)
        # elastic gradient path: local loss+grads, host-allreduce mean,
        # then a jitted optax apply — the data plane XLA cannot resize,
        # the host collective can
        loss_and_grad = jax.jit(jax.value_and_grad(
            lambda p, ids, labels: unsharded_loss(p, ids, labels, cfg)))

        @jax.jit
        def apply_update(p, o, grads):
            updates, o2 = optimizer.update(grads, o, p)
            return optax.apply_updates(p, updates), o2

    per_part = 8  # records per partition per batch
    feed = recordio_feed(uri, mesh, batch_records=per_part,
                         max_bytes=(SEQ + 1) * 4,
                         world=trainer.world if trainer else None)
    from dmlc_tpu import telemetry
    from dmlc_tpu.models import train_flops_per_token

    telemetry.declare_flops_per_token(train_flops_per_token(cfg, SEQ))
    done = 0
    # non-elastic: done counts NEW steps this process trains; saves are
    # numbered base+done so a resumed run never re-commits old numbers
    base = start_at or 0
    # data fast-forward: this feed is deterministic, so replaying the
    # checkpoint's persisted stream position puts the stream exactly
    # where the saved run was — including batches a self-heal skip
    # consumed without training (step count alone under-counts those).
    # Pre-PR checkpoints have no position; start_at approximates it.
    # (a demo-grade skip — it pays full pipeline + transfer cost per
    # discarded batch; production resumes would skip at the host side)
    skip = (start_at or 0) if stream_resume is None else stream_resume
    if elastic and start_at:
        # elastic restores are repartition points, not replays: done is
        # the ABSOLUTE step (base stays 0) and the stream restarts
        done = start_at
        skip = 0
    from dmlc_tpu.resilience import SelfHealGuard

    # without a checkpoint dir there is nothing to roll back to, so
    # the escalation ladder caps at skip -> abort
    guard = SelfHealGuard(**({} if manager is not None
                             else {"max_rollbacks": 0}))

    # rollback target when poison strikes before the first commit:
    # "replaying from step 0" must really mean the pre-training state
    # (jax arrays are immutable, so these references are a free undo) —
    # returning the already-trained params with done=0 would re-train
    # the consumed batches on top of them and desync step count from
    # optimizer state.  Dropped after the first commit (and never
    # captured in elastic mode, whose rollback restores via the
    # trainer) so it doesn't pin a second params+opt copy all run
    genesis = ((params, opt_state, done, skip)
               if trainer is None and manager is not None else None)

    feed_iter = iter(feed)
    loss = float("nan")
    need_resync = False
    # job-level goodput accounting (telemetry.goodput): explicit enter()
    # hooks mark the intervals the span surfaces can't see — the elastic
    # recovery window (WorldResized raise -> resync settled) and the
    # self-heal rollback + replay.  The resize path must RE-ENTER the
    # interval it was in before the raise (e.g. a feed wait), else the
    # whole recovery leaks into idle/unattributed
    from dmlc_tpu.telemetry import goodput as goodput_ledger

    goodput_ledger.ledger()  # opt this process into goodput heartbeats
    resize_active = False    # a resize episode is open
    resize_prev = None       # override to restore when it settles
    rollback_until = None    # replaying until done reaches this step
    rollback_prev = None
    # done-value at the current stream's batch 0: the deterministic
    # feed means "replay to step A" = fast-forward (A - stream_base)
    # quality batches from a fresh stream.  Non-elastic streams always
    # start at step 0; an elastic stream restarts at each resync (the
    # partitioning changed), so its base is the resync step
    stream_base = done if elastic else 0
    # exact stream position: quality batches consumed from the current
    # partitioning's deterministic sequence (self-heal skips consume a
    # batch WITHOUT advancing `done`, so the step count alone
    # under-counts the position).  `stream_gen` names the partitioning
    # (bumped at each elastic resync); `ckpt_consumed` snapshots the
    # position at every commit so a rollback replays the exact count
    consumed = 0
    stream_gen = 0
    ckpt_consumed = {}  # absolute committed step -> (stream_gen, consumed)

    def rollback_and_replay(params, opt_state, done, base, stream_base):
        """Self-heal rollback: restore the last committed checkpoint
        and set up the deterministic replay — the feed restarts and
        fast-forwards back to the restored step (a rollback, unlike a
        resize, changes no membership, so the per-rank stream is
        reproducible).  The replay count is the position snapshotted at
        commit (falling back to the step arithmetic for checkpoints
        from before this process / partitioning).  Quarantined spans
        are skip-listed out of the replay by the readers, which is
        exactly how the job routes around poisoned bytes."""
        if trainer is not None:
            params, opt_state, done = trainer.rollback(
                feed, params, opt_state, done)
            snap = ckpt_consumed.get(done)
            if snap is not None and snap[0] == stream_gen:
                print(f"selfheal: replaying {snap[1]} batches",
                      flush=True)
                return params, opt_state, done, snap[1], base, stream_base
            if done >= stream_base:
                print(f"selfheal: replaying {done - stream_base} batches",
                      flush=True)
                return (params, opt_state, done, done - stream_base,
                        base, stream_base)
            # restored state predates this stream (an older committed
            # step survived a resize): restart the stream at it
            return params, opt_state, done, 0, base, done
        restored_step, restored, stream_pos = _restore_with_stream(
            manager, {"params": params, "opt": opt_state}, mesh,
            with_stream=trainer is None)
        feed.close()  # abandon the in-flight epoch
        if restored_step is None:
            # poisoned before the first save: the genesis state replays
            if genesis is None:
                raise RuntimeError(
                    "selfheal: no committed checkpoint and no genesis "
                    "state to roll back to")
            g_params, g_opt, g_done, g_skip = genesis
            print("selfheal: no committed checkpoint; rolling back to "
                  "the genesis state", flush=True)
            return g_params, g_opt, g_done, g_skip, base, 0
        params, opt_state = restored["params"], restored["opt"]
        if restored_step < base:
            base = restored_step
        snap = ckpt_consumed.get(restored_step)
        if snap is not None and snap[0] == stream_gen:
            replay = snap[1]
        elif stream_pos is not None:
            replay = stream_pos
        else:
            replay = restored_step  # pre-position checkpoint
        print(f"selfheal: rolled back to committed step {restored_step};"
              f" replaying {replay} batches", flush=True)
        return (params, opt_state, restored_step - base, replay,
                base, 0)

    while done < steps:
        # the step ledger opens BEFORE the batch pull so the feed's
        # consumer wait (feed.wait span) is billed to this step's
        # feed-wait share; skipped/tail batches abandon the open step
        # (the next step_begin unwinds it) and are never recorded
        telemetry.step_begin()
        try:
            if trainer is not None:
                if need_resync:
                    params, opt_state, done = trainer.resync(
                        feed, params, opt_state, done)
                    feed_iter = iter(feed)
                    stream_base = done  # repartitioned: fresh stream
                    stream_gen += 1    # old positions are incomparable
                    consumed = 0
                    # a resize landing mid-rollback-replay voids the
                    # replay plan with it: a leftover skip would drop
                    # never-trained batches from the fresh stream
                    skip = 0
                    need_resync = False
                    if resize_active:
                        # generation settled: re-enter the pre-resize
                        # interval.  A voided rollback replay does NOT
                        # resume (skip was just reset) — its episode
                        # ends with the resize
                        if resize_prev == "rollback_replay":
                            rollback_until = None
                            resize_prev = rollback_prev
                        goodput_ledger.enter(resize_prev)
                        resize_active = False
                trainer.client.check_resized()
            batch = next(feed_iter, None)
            if batch is None:
                feed_iter = iter(feed)  # next epoch
                continue
            # epoch-tail short batch: its zero-padded rows would train on
            # all-zero tokens (garbage targets).  Dropped BEFORE the
            # resume fast-forward so never-trained batches don't consume
            # `skip` — step count stays equal to trained-batch count
            if np.any(np.asarray(batch["length"]) == 0):
                continue
            consumed += 1
            if skip > 0:
                skip -= 1
                continue
            # the pre-step references are the free undo for a skipped
            # (poisoned) step: jax arrays are immutable
            prev_params, prev_opt = params, opt_state
            with metrics.annotate("train_step"):
                data = jnp.asarray(batch["data"])
                toks = jax.lax.bitcast_convert_type(
                    data.reshape(-1, SEQ + 1, 4), jnp.int32
                ).reshape(-1, SEQ + 1)
                ids, labels = toks[:, :-1], toks[:, 1:]
                if trainer is None:
                    params, opt_state, loss, gnorm = step(
                        params, opt_state, ids, labels)
                else:
                    local_loss, grads = loss_and_grad(params, ids, labels)
                    grads, loss, gnorm = trainer.allreduce_grads(
                        grads, float(local_loss))
                    params, opt_state = apply_update(params, opt_state,
                                                     grads)
            action = guard.observe(float(loss), grad_norm=float(gnorm),
                                   step=done + 1)
            if action == "skip":
                params, opt_state = prev_params, prev_opt
                continue
            if action == "rollback":
                # rollback_replay covers the restore AND the re-executed
                # steps (work lost = steps redone x prior step time):
                # the override stays up until `done` regains this step
                if rollback_until is None:
                    rollback_prev = goodput_ledger.enter("rollback_replay")
                rollback_until = max(rollback_until or 0, done)
                (params, opt_state, done, skip, base,
                 stream_base) = rollback_and_replay(
                    prev_params, prev_opt, done, base, stream_base)
                feed_iter = iter(feed)
                consumed = 0  # fresh stream: the replay re-counts
                continue
            if action == "abort":
                guard.raise_abort(done + 1)
        except WorldResized:
            # recovery happens at the top of the next iteration (the
            # resync broadcast can itself hit another resize, and it
            # must run under this same handler)
            prev = goodput_ledger.enter("resize")
            if not resize_active:
                # only the FIRST raise of an episode captures the
                # pre-resize interval (a resize landing mid-resync
                # re-raises here with the override already "resize")
                resize_prev = prev
                resize_active = True
            need_resync = True
            continue
        telemetry.step_end(tokens=int(ids.size))
        done += 1
        if rollback_until is not None and done >= rollback_until:
            # replay caught back up: the lost work is repaid
            goodput_ledger.enter(rollback_prev)
            rollback_until = None
        if done % 10 == 0 or done == 1:
            print(f"step {done}: loss {float(loss):.4f}", flush=True)
        if manager is not None and done % 20 == 0:
            # every rank snapshots the stream position at the commit
            # boundary (a later rollback replays exactly this count);
            # non-elastic checkpoints persist it for exact resume
            ckpt_consumed[base + done] = (stream_gen, consumed)
            if trainer is None or trainer.client.rank == 0:
                tree = {"params": params, "opt": opt_state}
                if trainer is None:
                    tree["stream"] = np.asarray([consumed], np.int64)
                manager.save(base + done, tree)
                genesis = None  # a committed checkpoint outranks it
    if manager is not None and done % 20 != 0 \
            and (trainer is None or trainer.client.rank == 0):
        # periodic save already hit on multiples of 20
        tree = {"params": params, "opt": opt_state}
        if trainer is None:
            tree["stream"] = np.asarray([consumed], np.int64)
        manager.save(base + done, tree)
    if trainer is not None:
        trainer.close()
    snap = metrics.snapshot()
    fed = snap.get("feed", {})
    led = telemetry.ledger().summary()
    print(f"final loss {float(loss):.4f}; feed moved "
          f"{fed.get('bytes_to_device', 0) / 1e6:.1f} MB in "
          f"{int(fed.get('batches', 0))} batches")
    if led:
        mfu = led.get("mfu")
        print(f"ledger: step p50 {led['step_time_p50'] * 1e3:.1f} ms, "
              f"p99 {led['step_time_p99'] * 1e3:.1f} ms, feed-wait "
              f"{led['feed_wait_fraction'] * 100:.0f}%, collective "
              f"exposed {led['collective_exposed_fraction'] * 100:.0f}%"
              f" / overlapped "
              f"{led['collective_overlapped_fraction'] * 100:.0f}%, "
              f"goodput {led.get('goodput_tokens_per_s', 0):,.0f} tok/s"
              + (f", MFU {mfu * 100:.1f}%" if mfu is not None else ""))


if __name__ == "__main__":
    main()
