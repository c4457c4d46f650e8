"""Distributed JAX worker: tracker rendezvous + cross-process psum.

Proves the full data-plane story the reference's multi-node jobs rely on
(tracker/dmlc_tracker/tracker.py:410-433 launching real workers): each
process launched by dmlc-submit

  1. rendezvouses with the rabit tracker (host control plane),
  2. calls initialize_distributed() — jax.distributed over the
     tracker-allocated DMLC_JAX_COORD_URI/PORT (never the rabit socket),
  3. joins one global device mesh spanning all processes, and
  4. verifies a cross-process psum against the closed-form answer.

Run under the launcher:
    bin/dmlc-submit --cluster local --num-workers 2 -- \
        python examples/jax_psum_worker.py

On CPU hosts (CI) the gloo collectives implementation backs the psum; on
TPU pods the same code runs over ICI with no change.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# N workers share this host and a chip belongs to one process, so the
# demo defaults to the CPU backend; pinned before first backend use,
# through the environment and jax.config both.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402

if os.environ.get("JAX_PLATFORMS", "") == "cpu":
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from dmlc_tpu.parallel.collectives import initialize_distributed  # noqa: E402
from dmlc_tpu.tracker.client import TrackerClient  # noqa: E402


def main():
    client = TrackerClient()
    client.start()
    rank, world = client.rank, client.world_size

    initialize_distributed()
    assert jax.process_count() == world, (jax.process_count(), world)
    devs = jax.devices()  # global: spans every process in the job
    n_local = len(jax.local_devices())

    mesh = Mesh(np.array(devs), ("dp",))
    local = jnp.full((n_local,), float(rank + 1))
    garr = jax.make_array_from_single_device_arrays(
        (len(devs),), NamedSharding(mesh, P("dp")),
        [jax.device_put(local[i : i + 1], d)
         for i, d in enumerate(jax.local_devices())])
    total = jax.jit(lambda a: jnp.sum(a) / n_local,
                    out_shardings=NamedSharding(mesh, P()))(garr)
    got = float(total)
    want = world * (world + 1) / 2
    assert got == want, (got, want)
    client.log(f"rank {rank}/{world}: jax psum OK -> {got}")
    client.shutdown()


if __name__ == "__main__":
    main()
