"""What the chip will be asked to compile, checked without a chip.

Tier-1 forces the CPU, where ops/dispatch.py never takes a kernel
branch — so a kernel the TPU refuses, or one that cannot be traced the
way ``make_train_step`` calls it, used to be invisible here.  Two
guards:

* every Pallas kernel is cross-lowered for the TPU from the CPU at the
  flagship shapes ``chip_smoke.py`` runs.  This reaches the Pallas TPU
  lowering (BlockSpec tiling rules, unsupported primitives) but not
  Mosaic's own compile, which only the chip run sees;
* the kernel branch is driven (interpret mode, flipped through the one
  dispatch function) through ``make_train_step`` on 1- and 4-device
  meshes, with loss parity against ``unsharded_loss``.

And one about processes: a chip belongs to whichever process touched
JAX's backend first, so importing the package must never do that.
"""

import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dmlc_tpu import telemetry
from dmlc_tpu.models import (TransformerConfig, init_params,
                             make_train_step, unsharded_loss)
from dmlc_tpu.ops import dispatch
from dmlc_tpu.ops import flash_attention as flash
from dmlc_tpu.ops import paged_attention as paged
from dmlc_tpu.parallel import build_mesh

# (B, T, H, D) of the two flagship training shapes
FLAGSHIP_SHAPES = [(8, 1024, 16, 128), (1, 8192, 16, 128)]


def _lower_for_tpu(fn, *avals) -> str:
    with dispatch.force_kernel_mode(dispatch.MOSAIC):
        text = jax.jit(fn).trace(*avals).lower(
            lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    return text


def _one_device_mesh():
    return build_mesh(1, dp=1, sp=1, tp=1, pp=1, ep=1)


@pytest.mark.parametrize("shape", FLAGSHIP_SHAPES)
def test_flash_kernels_lower_for_tpu_inside_shard_map(shape):
    """Forward, dkv and dq at the flagship shapes, called the way the
    train step calls them: inside a VMA-checked shard_map."""
    spec = P("dp", "sp", "tp", None)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(flash.flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    grad = jax.shard_map(jax.grad(loss, argnums=(0, 1, 2)),
                         mesh=_one_device_mesh(),
                         in_specs=(spec, spec, spec),
                         out_specs=(spec, spec, spec))
    text = _lower_for_tpu(grad, x, x, x)
    for name in ("flash_fwd", "flash_dkv", "flash_dq"):
        assert name in text, f"{name} missing from the lowered step"


def test_ring_step_kernel_lowers_for_tpu_inside_shard_map():
    spec = P("dp", "sp", "tp", None)
    x = jax.ShapeDtypeStruct((1, 4096, 16, 128), jnp.bfloat16)

    def step(q, k, v):
        my = jax.lax.axis_index("sp")
        return flash.block_attend_flash(
            q, k, v, scale=128 ** -0.5, causal=True,
            q_offset=my * 4096, kv_offset=my * 4096)

    out = (spec, P("dp", "tp", "sp"), P("dp", "tp", "sp"))
    fn = jax.shard_map(step, mesh=_one_device_mesh(),
                       in_specs=(spec, spec, spec), out_specs=out)
    _lower_for_tpu(fn, x, x, x)


@pytest.mark.parametrize("b,s_w,n_blocks,w,dtype", [
    (8, 1, 256, 66, jnp.bfloat16),
    (8, 4, 256, 66, jnp.bfloat16),
    (8, 1, 256, 66, jnp.float32),
    (8, 4, 256, 66, jnp.float32),
    # the flagship serve cells' own: 32 rows, all layers' pages as one
    # run, the narrowest, the chat and the doc tables
    (32, 1, 16 * 2560, 4, jnp.bfloat16),
    (32, 1, 16 * 2560, 24, jnp.bfloat16),
    (32, 1, 16 * 2560, 130, jnp.bfloat16),
])
def test_paged_attention_lowers_for_tpu(b, s_w, n_blocks, w, dtype):
    """H=16, block 16: the shape whose per-head [bs, 1, D] block the TPU
    lowering refused.  One call of the function is ONE custom call
    named ``paged_attn``: the benchmark's roofline reader multiplies
    the kernel's cost by the events of that name."""
    h, d, bs = 16, 128, 16
    assert paged.supports(d, bs, h)
    q = jax.ShapeDtypeStruct((b, s_w, h, d), dtype)
    pool = jax.ShapeDtypeStruct((n_blocks, bs, h, d), dtype)
    tables = jax.ShapeDtypeStruct((b, w), jnp.int32)
    lengths = jax.ShapeDtypeStruct((b,), jnp.int32)
    text = _lower_for_tpu(paged.paged_attention,
                          q, pool, pool, tables, lengths)
    assert text.count("stablehlo.custom_call") == 1
    assert text.count('kernel_name = "paged_attn"') == 1


@pytest.fixture(scope="module")
def one_chip(request):
    """A described v5e chip, nothing attached: what ``.compile()`` for
    it refuses, the chip's compiler would.  Only this file asks for it,
    inside a test (one process may hold the TPU's library)."""
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_paged_attention_compiles_to_one_named_op(one_chip):
    """Compiled for the chip with the o projection behind it, at the
    chat cell's shapes: Mosaic takes the kernel (DMAs, semaphores,
    scalar trip counts, 32 MB of scoped VMEM), and the ONLY instruction
    of the optimized program whose op_name carries ``paged_attn`` is
    the kernel's custom call: a layout copy or a cast under that name
    would be a second event to the roofline's reader."""
    import re

    b, h, d, bs, w, e = 32, 16, 128, 16, 24, 2048
    arr = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    pool = arr((16 * 2560, bs, h, d), jnp.bfloat16)

    def layer(q, kp, vp, tables, lengths, wo):
        o = paged.paged_attention(q, kp, vp, tables, lengths)
        return jnp.einsum("bthd,hde->bte", o, wo)

    with dispatch.force_kernel_mode(dispatch.MOSAIC):
        hlo = jax.jit(layer).lower(
            arr((b, 1, h, d), jnp.bfloat16), pool, pool,
            arr((b, w), jnp.int32), arr((b,), jnp.int32),
            arr((h, d, e), jnp.bfloat16)).compile().as_text()
    named = [line for line in hlo.splitlines()
             if re.search(r'op_name="[^"]*paged_attn', line)]
    assert len(named) == 1, named
    assert "custom-call(" in named[0] and "tpu_custom_call" in named[0]


def test_decode_program_copies_no_weight_out_of_a_stack(one_chip):
    """The flagship's decode program as the engine jits it, compiled for
    the chip at the chat cell's shapes.  From the stacked tree XLA
    materialises per-layer slices of the stack under the op name a
    trace shows them by (``jit(forward_decode_paged)/slice``: 403 MB
    written a step); from the tree the engine holds
    (``per_layer_params``) no instruction carries that name and the
    program's temporaries are smaller by eight FFN matrices' bytes at
    the least (measured here: 290.6 MB against 21.6 MB)."""
    from dmlc_tpu.models import transformer as tfm

    cfg = tfm.flagship_config()
    rows, w, n_blocks, bs = 32, 24, 2560, 16
    arr = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    on_chip = lambda a: arr(a.shape, a.dtype)  # noqa: E731
    stacked = jax.tree.map(on_chip, jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    layered = jax.tree.map(on_chip, jax.eval_shape(
        tfm.per_layer_params, stacked))
    assert len(layered["layers"]) == cfg.n_layers
    pool = arr((cfg.n_layers, n_blocks, bs, cfg.n_heads, cfg.head_dim),
               cfg.jdtype)
    col = arr((rows, 1), jnp.int32)
    program = jax.jit(tfm.picking_decode(tfm.forward_decode_paged),
                      static_argnums=(7,), donate_argnums=(3, 4))
    temp, named = {}, {}
    with dispatch.force_kernel_mode(dispatch.MOSAIC):
        for name, tree in (("stacked", stacked), ("layered", layered)):
            compiled = program.lower(
                tree, (col, col, arr((rows,), jnp.int32)), col, pool, pool,
                arr((rows, w), jnp.int32), arr((rows,), jnp.int32),
                cfg).compile()
            temp[name] = compiled.memory_analysis().temp_size_in_bytes
            named[name] = compiled.as_text().count(
                'op_name="jit(forward_decode_paged)/slice"')
    assert named["stacked"] > 0 and named["layered"] == 0, named
    ffn_matrix = cfg.d_model * cfg.d_ff * 2
    assert temp["layered"] < temp["stacked"] - 8 * ffn_matrix, temp


def test_kda_state_step_compiles_and_updates_the_state_in_place(one_chip):
    """Compiled for the chip inside a layer loop, at the reasoning
    cell's shapes (64 rows, 32 heads of 128 x 128, all 11 KDA layers'
    slots as one run, donated): Mosaic takes the kernel, the state
    passes from call to call as the same buffer, and no instruction of
    the program copies or slices an array of the state's size."""
    import re

    from dmlc_tpu.ops import kda

    b, h, d, layers, n_slots = 64, 32, 128, 11, 64
    arr = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    vec = arr((b, h, d), jnp.float32)

    def two_layers(q, k, v, g, beta, state, slots, live):
        flat = state.reshape((-1,) + state.shape[2:])
        o = 0.0
        for li in (3, 7):
            o_li, flat = kda.kda_state_step(q, k, v, g, beta, flat,
                                            slots + li * n_slots, live)
            o = o + o_li
        return o, flat.reshape(state.shape)

    with dispatch.force_kernel_mode(dispatch.MOSAIC):
        compiled = jax.jit(two_layers, donate_argnums=(5,)).lower(
            vec, vec, vec, vec, arr((b, h), jnp.float32),
            arr((layers, n_slots, h, d, d), jnp.float32),
            arr((b,), jnp.int32), arr((b,), jnp.bool_)).compile()
    hlo = compiled.as_text()
    calls = [line for line in hlo.splitlines()
             if "custom-call(" in line and "kda_state_step" in line]
    assert len(calls) == 2 and all("tpu_custom_call" in c for c in calls)
    state_sized = re.compile(r"= f32\[(704|11,64),?32,128,128\]\S* (\w[\w-]*)\(")
    ops = {m.group(2) for m in map(state_sized.search, hlo.splitlines())
           if m}
    assert ops <= {"bitcast", "get-tuple-element", "parameter"}, ops
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= layers * n_slots * h * d * d * 4
    assert m.temp_size_in_bytes < 64 * 2 ** 20


@pytest.mark.parametrize("span,width", [(0, 257), (4096, 33)],
                         ids=["full", "sliding"])
def test_grouped_and_windowed_kernels_compile_for_the_chip(one_chip, span,
                                                           width):
    """Compiled for the chip at Command A+'s shapes (128 query heads on
    8 K/V heads of 128, window 4096, block 128): the prefill kernel on
    a chunk of 8k rows at a traced offset against a 32k prompt's whole
    K/V, and the decode kernel of 8 rows over a full table of 257
    entries or a ring of 33, all layers' pages as one run.  Mosaic takes
    both (the K/V index maps that clamp masked blocks, the window's
    shortened grid, the ring's modulus in the page walk), each as ONE
    custom call under its kernel's name."""
    bf = jnp.bfloat16
    arr = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    kv = arr((1, 32768, 8, 128), bf)
    pool = arr((3 * 264, 128, 8, 128), bf)
    assert paged.supports(128, 128, 8)
    with dispatch.force_kernel_mode(dispatch.MOSAIC):
        prefill = jax.jit(lambda q, k, v, off: flash.flash_attention(
            q, k, v, span=span, q_offset=off)).lower(
                arr((1, 8192, 128, 128), bf), kv, kv,
                arr((), jnp.int32)).compile().as_text()
        decode = jax.jit(lambda q, k, v, t, n: paged.paged_attention(
            q, k, v, t, n, span=span)).lower(
                arr((8, 1, 128, 128), bf), pool, pool,
                arr((8, width), jnp.int32),
                arr((8,), jnp.int32)).compile().as_text()
    for hlo, kernel in ((prefill, "flash_fwd_o"), (decode, "paged_attn")):
        calls = [line for line in hlo.splitlines()
                 if "custom-call(" in line and kernel in line]
        assert len(calls) == 1 and "tpu_custom_call" in calls[0], calls


def test_dispatch_is_one_flippable_function():
    counts = lambda: telemetry.counters_snapshot().get("kernels", {})  # noqa: E731
    assert dispatch.kernel_mode() == dispatch.LAX  # tier-1 is CPU
    with dispatch.force_kernel_mode(dispatch.INTERPRET):
        before = counts().get("interpret_traces", 0)
        assert dispatch.choose(True) == dispatch.INTERPRET
        assert dispatch.choose(False) == dispatch.LAX
        assert dispatch.choose(True, "lax") == dispatch.LAX
        assert counts()["interpret_traces"] == before + 1
    assert dispatch.kernel_mode() == dispatch.LAX
    assert dispatch.choose(False, "pallas") == dispatch.INTERPRET
    with pytest.raises(ValueError):
        with dispatch.force_kernel_mode("cuda"):
            pass


@pytest.mark.parametrize("axes", [
    {"dp": 1, "sp": 1, "tp": 1},
    {"dp": 2, "sp": 2, "tp": 1},
    {"dp": 2, "sp": 1, "tp": 2},
])
def test_train_step_through_kernel_branch_matches_oracle(axes):
    """``make_train_step`` with every attention call on the kernel
    branch: the standalone flash kernels (fwd + dkv + dq) where sp == 1
    and the ring-step kernel where sp == 2."""
    n = axes["dp"] * axes["sp"] * axes["tp"]
    mesh = build_mesh(n, pp=1, ep=1, **axes)
    cfg = TransformerConfig(vocab=256, d_model=64, n_heads=2, head_dim=128,
                            d_ff=128, n_layers=2, n_experts=1,
                            microbatches=1, dtype="float32", remat=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0, cfg.vocab)
    labels = jnp.roll(ids, -1, axis=1)
    want = float(unsharded_loss(params, ids, labels, cfg))

    before = telemetry.counters_snapshot().get("kernels", {})
    with dispatch.force_kernel_mode(dispatch.INTERPRET):
        step, init_state = make_train_step(mesh, cfg, ledger=False)
        opt_state = init_state(params)
        params, opt_state, loss0 = step(params, opt_state, ids, labels)
        params, opt_state, loss1 = step(params, opt_state, ids, labels)
    after = telemetry.counters_snapshot().get("kernels", {})
    assert (after.get("interpret_traces", 0)
            > before.get("interpret_traces", 0))
    assert after.get("lax_traces", 0) == before.get("lax_traces", 0)
    assert abs(float(loss0) - want) < 1e-4 * max(abs(want), 1.0)
    assert float(loss1) < float(loss0)


def test_importing_the_package_initialises_no_backend():
    """The load generator, the launcher and chip_smoke.py's bookkeeping
    all import dmlc_tpu next to a process that owns the chip; an import
    that created a backend would take the chip from it (or hang)."""
    code = (
        "import importlib, pkgutil, dmlc_tpu\n"
        "for m in pkgutil.walk_packages(dmlc_tpu.__path__, 'dmlc_tpu.'):\n"
        "    if '.lib' not in m.name:  # the ctypes-loaded native .so files\n"
        "        importlib.import_module(m.name)\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, list(xla_bridge._backends)\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)
