"""Distributed logistic-regression SGD on a LibSVM file — the minimum
end-to-end slice (SURVEY.md §7): every layer of the framework at once.

  dmlc-submit --cluster local --num-workers N -- \
      python examples/train_libsvm.py <uri> [epochs]

Each worker: rendezvous via the tracker (rank/world), reads InputSplit
partition rank/world of the file, computes logistic-loss gradients in
JAX, and synchronizes gradients with the tracker client's binomial-tree
allreduce (the host-side control-plane path; on a TPU pod the same step
runs under pjit with lax.psum over the mesh instead — parallel/).
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
# host-side SGD demo: many workers share one host and a chip belongs to
# one process at a time, so default to the CPU backend; export
# JAX_PLATFORMS yourself to target an accelerator
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main():
    uri = sys.argv[1] if len(sys.argv) > 1 else None
    epochs = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    assert uri, "usage: train_libsvm.py <uri> [epochs]"

    import jax
    import jax.numpy as jnp

    from dmlc_tpu.data import create_row_iter
    from dmlc_tpu.feed.device_feed import pack_rowblock
    from dmlc_tpu.tracker.client import TrackerClient

    client = TrackerClient()
    client.start()
    rank, world = client.rank, client.world_size

    it = create_row_iter(uri, rank, world, "libsvm")
    # feature-count must agree across workers for the weight vector
    num_col = int(client.allreduce(
        np.array([it.num_col()], np.int64), op="max")[0])
    num_col = max(num_col, 1)

    @jax.jit
    def grad_step(w, value, index, mask, label):
        def loss_fn(w):
            x = (value * mask)  # [B, K]
            logits = jnp.sum(x * w[index], axis=1)
            p = jax.nn.sigmoid(logits)
            eps = 1e-7
            return -jnp.mean(
                label * jnp.log(p + eps) + (1 - label) * jnp.log(1 - p + eps)
            )
        return jax.value_and_grad(loss_fn)(w)

    # pack this partition's rows once; byte-range partitions are NOT
    # row-balanced, so workers agree on a global step count and pad with
    # zero-mask batches — otherwise allreduce calls desynchronize
    batches = []
    for blk in it:
        for lo in range(0, blk.size, 256):
            sub = blk.slice(lo, min(lo + 256, blk.size))
            batches.append(pack_rowblock(sub, 256, 64, num_col))
    n_steps = int(client.allreduce(
        np.array([len(batches)], np.int64), op="max")[0])
    zero = {"label": np.zeros(256, np.float32),
            "value": np.zeros((256, 64), np.float32),
            "index": np.zeros((256, 64), np.int32),
            "mask": np.zeros((256, 64), np.float32)}

    w = jnp.zeros(num_col, jnp.float32)
    lr = 0.5
    for epoch in range(epochs):
        total_loss = 0.0
        for i in range(n_steps):
            b = batches[i] if i < len(batches) else zero
            loss, g = grad_step(w, b["value"], b["index"], b["mask"],
                                b["label"])
            g_sum = client.allreduce_sum(np.asarray(g, np.float64))
            w = w - lr * jnp.asarray(g_sum / world, jnp.float32)
            total_loss += float(loss)
        client.log(
            f"rank {rank}: epoch {epoch} loss "
            f"{total_loss / max(len(batches), 1):.4f} "
            f"({len(batches)}/{n_steps} local batches)"
        )
    client.shutdown()


if __name__ == "__main__":
    main()
