#!/usr/bin/env python3
"""Rehearsal 3: compile the cells' real shapes for a DESCRIBED v5e:2x2.

Nothing runs and no chip is attached, so this says nothing about speed
or results: it shows what the chip's compiler would refuse (a kernel, a
program that does not fit) and what ``memory_analysis()`` counts for
ONE program (arguments + outputs + temporaries; not what else the
process keeps on the device).  It fixed ``n_blocks`` and the ring
cell's T.  Run it here, on the CPU:

  JAX_PLATFORMS=cpu python benchmarks/rehearsals/compile_real_shapes.py \
      train:flagship-1b-train:8:1024 \
      train:flagship-1b-train-ring4:2:16384 \
      decode:flagship-1b-serve:24 prefill:flagship-1b-serve:2048

Each argument is ``train:<config>:<B>:<T>``, ``decode:<config>:<W>``
(block-table width) or ``prefill:<config>:<T>``.
"""

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# plain jax.jit from profiled_jit, so .lower() is the public one
os.environ["DMLC_COMPUTE_PROFILE"] = "0"

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def _analysis(compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    return {"argument_gb": m.argument_size_in_bytes / 1e9,
            "output_gb": m.output_size_in_bytes / 1e9,
            "temp_gb": m.temp_size_in_bytes / 1e9,
            "alias_gb": m.alias_size_in_bytes / 1e9,
            "total_gb": total / 1e9}


def main(argv):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from dmlc_tpu.models import transformer as tfm
    from dmlc_tpu.ops import dispatch
    from dmlc_tpu.parallel import build_mesh

    # these compiles cannot be read back from the persistent cache
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    results = []
    for spec in argv:
        what, config_name, *sizes = spec.split(":")
        with open(os.path.join(ROOT, "benchmarks", "configs",
                               config_name + ".json")) as f:
            config = json.load(f)
        cfg = tfm.TransformerConfig(**config["model"])
        t0 = time.monotonic()
        # ops/dispatch.py asks jax.default_backend(), which is the CPU
        # here; its test hook says what the chip would answer
        with dispatch.force_kernel_mode(dispatch.MOSAIC):
            if what == "train":
                b, t = (int(s) for s in sizes)
                mesh = build_mesh(devices=topo.devices[:config["chips"]],
                                  **config["mesh"])
                shapes = jax.eval_shape(
                    lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
                params = jax.tree.map(
                    lambda s, spec: jax.ShapeDtypeStruct(
                        s.shape, s.dtype, sharding=NamedSharding(mesh, spec)),
                    shapes, tfm.param_specs())
                step, init_state = tfm.make_train_step(mesh, cfg,
                                                       ledger=False)
                opt = jax.eval_shape(init_state, params)
                # optimizer state follows its parameter's sharding, as
                # jit propagates it on the chip
                flat_p = {tuple(s.shape): s.sharding
                          for s in jax.tree.leaves(params)}
                opt = jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct(
                        s.shape, s.dtype,
                        sharding=flat_p.get(tuple(s.shape),
                                            NamedSharding(mesh, P()))),
                    opt)
                ids = jax.ShapeDtypeStruct(
                    (b, t), jnp.int32,
                    sharding=NamedSharding(mesh, P("dp", "sp")))
                compiled = step.lower(params, opt, ids, ids).compile()
            else:
                one = SingleDeviceSharding(topo.devices[0])
                shapes = jax.eval_shape(
                    lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
                params = jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                   sharding=one), shapes)

                def arr(shape, dtype):
                    return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

                if what == "decode":
                    w = int(sizes[0])
                    sv = config["serve"]
                    rows = sv["max_active"]
                    pool = arr((cfg.n_layers, sv["n_blocks"],
                                sv["block_size"], cfg.n_heads,
                                cfg.head_dim), cfg.jdtype)
                    compiled = jax.jit(
                        tfm.forward_decode_paged, static_argnums=(7,)
                    ).lower(params, arr((rows, 1), jnp.int32),
                            arr((rows, 1), jnp.int32), pool, pool,
                            arr((rows, w), jnp.int32),
                            arr((rows,), jnp.int32), cfg).compile()
                elif what == "prefill":
                    t = int(sizes[0])
                    compiled = jax.jit(
                        tfm.forward_prefill_last, static_argnums=(3,)
                    ).lower(params, arr((1, t), jnp.int32),
                            arr((1,), jnp.int32), cfg).compile()
                else:
                    raise SystemExit(f"unknown rehearsal {spec!r}")
        hlo = compiled.as_text()
        doc = {"rehearsal": spec, "NOT_A_CHIP_RUN": True,
               "compile_s_on_this_cpu": round(time.monotonic() - t0, 1),
               "kernels": sorted(k for k in (
                   "flash_fwd", "flash_dkv", "flash_dq", "paged_attn")
                   if k in hlo),
               "collectives": {k: hlo.count(k + "(") + hlo.count(
                   k + "-start(") for k in (
                       "all-reduce", "collective-permute", "all-gather",
                       "reduce-scatter", "all-to-all")},
               **_analysis(compiled)}
        print(json.dumps(doc), flush=True)
        results.append(doc)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
