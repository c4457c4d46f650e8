"""Distributed linear-regression SGD on a CSV file (BASELINE config #3
shape: CSV tabular allreduce SGD via dmlc-submit).

  dmlc-submit --cluster local --num-workers N -- \
      python examples/train_csv.py <uri> [epochs] [label_column]

Each worker reads InputSplit partition rank/world of the CSV through the
parser registry (format=csv, native multi-threaded chunk parse when the
C++ library is available), computes squared-loss gradients in JAX, and
synchronizes them with the tracker client's tree allreduce.
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
    label_col = int(sys.argv[3]) if len(sys.argv) > 3 else 0
    assert uri, "usage: train_csv.py <uri> [epochs] [label_column]"

    import jax
    import jax.numpy as jnp

    from dmlc_tpu.data import create_row_iter
    from dmlc_tpu.feed.device_feed import pack_rowblock
    from dmlc_tpu.tracker.client import TrackerClient

    client = TrackerClient()
    client.start()
    rank, world = client.rank, client.world_size

    it = create_row_iter(f"{uri}?format=csv&label_column={label_col}",
                         rank, world, "auto")
    num_col = int(client.allreduce(
        np.array([it.num_col()], np.int64), op="max")[0])
    num_col = max(num_col, 1)

    @jax.jit
    def grad_step(w, value, index, mask, label):
        def loss_fn(w):
            pred = jnp.sum(value * mask * w[index], axis=1)
            return jnp.mean(jnp.square(pred - label))
        return jax.value_and_grad(loss_fn)(w)

    batches = []
    for blk in it:
        for lo in range(0, blk.size, 256):
            sub = blk.slice(lo, min(lo + 256, blk.size))
            batches.append(pack_rowblock(sub, 256, num_col, num_col))
    n_steps = int(client.allreduce(
        np.array([len(batches)], np.int64), op="max")[0])
    # explicit shapes: a rank with an EMPTY partition still needs padding
    # batches to stay in lockstep with the allreduce
    zero = {"label": np.zeros(256, np.float32),
            "value": np.zeros((256, num_col), np.float32),
            "index": np.zeros((256, num_col), np.int32),
            "mask": np.zeros((256, num_col), np.float32)}

    w = jnp.zeros(num_col, jnp.float32)
    lr = 0.1
    for epoch in range(epochs):
        total = 0.0
        for i in range(n_steps):
            b = batches[i] if i < len(batches) else zero
            loss, g = grad_step(w, b["value"], b["index"], b["mask"],
                                b["label"])
            g_sum = client.allreduce_sum(np.asarray(g, np.float64))
            w = w - lr * jnp.asarray(g_sum / world, jnp.float32)
            total += float(loss)
        client.log(f"rank {rank}: epoch {epoch} mse "
                   f"{total / max(len(batches), 1):.4f}")
    client.shutdown()


if __name__ == "__main__":
    main()
