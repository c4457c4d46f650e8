"""Flagship model: decoder-only transformer LM, 5-way parallel.

Parallelism map (axes from parallel.mesh):
  dp — batch sharding; gradient reduction via the loss pmean transpose
  pp — layer stages scheduled by parallel.pipeline (collective permute)
  sp — sequence sharding; exact ring attention (parallel.ring_attention)
  tp — megatron-style head/ffn/vocab sharding (psum at row-parallel outs)
  ep — MoE expert sharding with soft gating (psum over ep⊗tp)

One code path serves both the sharded SPMD body (inside jax.shard_map
with VMA checking, so psum/pvary transposes produce correct synced
gradients automatically) and the unsharded single-chip oracle
(ShardAxes()) — tests assert the two losses are bit-close.

MoE has two dispatch modes, both static-shaped for XLA: dense soft
gating (moe_topk=0 — every ep shard computes its local experts for all
tokens; exact, the correctness oracle) and top-k capacity routing
(moe_topk=k — each shard scatters only the (token, choice) pairs whose
expert it owns into [X_local, capacity, E] slots, so expert compute is
k/X of dense and sharded with no token exchange; overflow drops, the
standard static-shape trade).  Both combine with one psum over (ep, tp).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops.core import (
    ShardAxes,
    embed_lookup,
    layer_norm,
    rms_norm,
    rope,
    softmax_xent,
    swiglu_ffn,
)
from ..parallel.mesh import AXIS_DP, AXIS_EP, AXIS_PP, AXIS_SP, AXIS_TP
from ..parallel.pipeline import pipeline_spmd
from ..parallel.ring_attention import ring_attention, ring_attention_reference

SHARDED_AXES = ShardAxes(tp=AXIS_TP, sp=AXIS_SP, ep=AXIS_EP, pp=AXIS_PP, dp=AXIS_DP)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    head_dim: int = 16
    d_ff: int = 128
    n_layers: int = 4          # total; must divide by pp stages
    n_experts: int = 2         # 1 = dense FFN
    microbatches: int = 2      # pipeline schedule M
    dtype: str = "float32"     # bf16 for real runs; f32 for CPU tests
    remat: bool = False        # checkpoint each block (trade FLOPs for HBM)
    # remat_policy: "full" recomputes everything; "save_flash" keeps the
    # flash kernels' (o, lse) residuals — o is [B,T,H,hd] bf16 plus lse
    # [B,H,T] f32 PER LAYER — so the backward skips re-running the
    # forward attention kernel (+1-2% MFU on the single-chip flash path;
    # the sp-sharded ring path has no tagged residuals and falls back to
    # full remat regardless)
    remat_policy: str = "save_flash"
    moe_topk: int = 0          # 0 = dense soft gating; k>0 = routed top-k
    moe_capacity_factor: float = 1.25  # slots per expert vs perfect balance
    # observe capacity-overflow token drops via a metrics counter (debug
    # callback per step — off by default: it adds a host sync point)
    moe_debug_overflow: bool = False
    # ---- a second block family, served only (the paged path): latent
    # attention + a leading dense layer + sigmoid-routed dropless
    # experts with a shared expert (DeepSeek-V3's block, which A.X-K1
    # shares).  The defaults above and below leave the MHA tree and
    # programs exactly as they are.
    attention: str = "mha"       # "mla": multi-head latent attention
    q_lora_rank: int = 0         # MLA: query latent width (0: q = W_q xn)
    kv_lora_rank: int = 0        # MLA: the cached K/V latent width
    qk_nope_head_dim: int = 0    # MLA: per-head score width without RoPE
    qk_rope_head_dim: int = 0    # MLA: RoPE'd score width, one key for all heads
    # MLA: per-head value width; the widened MHA family: a value width
    # of its own (0: head_dim), whose K and V pages are then lane-flat
    # (:meth:`TransformerConfig.kv_pool_shapes`)
    v_head_dim: int = 0
    rope_theta: float = 10000.0
    # yarn (MLA only; factor 1 = plain RoPE): blended frequencies, and
    # the softmax scale times (0.1 * mscale_all_dim * ln factor + 1)^2
    rope_yarn_factor: float = 1.0
    rope_yarn_original: int = 4096
    rope_yarn_beta_fast: float = 32.0
    rope_yarn_beta_slow: float = 1.0
    rope_yarn_mscale_all_dim: float = 0.0
    # layers [0, n_dense_layers) carry the dense d_ff SwiGLU, the rest
    # experts; "sigmoid" routing is the dropless layer (_moe_held_ffn):
    # ``n_experts`` routed experts are HELD here, [moe_held_start,
    # moe_held_start + n_experts) of the router's moe_n_routed outputs
    n_dense_layers: int = 0
    moe_router: str = "softmax"  # "sigmoid": scores, top-k over all, renormalised
    moe_n_routed: int = 0        # router width (0: n_experts, all held)
    moe_held_start: int = 0
    moe_d_ff: int = 0            # width of one routed (and one shared) expert
    moe_n_shared: int = 0        # shared experts, computed for every token
    moe_routed_scale: float = 1.0
    # group-limited selection with a correction bias ("noaux_tc"): the
    # router's outputs in moe_n_group groups, a group scored by its two
    # largest biased scores, the picks taken inside the moe_topk_group
    # best groups by biased score and weighed by the unbiased one
    moe_n_group: int = 0         # 0: the picks range over all experts
    moe_topk_group: int = 0
    moe_router_bias: bool = False
    # ---- a third block family, served only: ``attention="kda_mla"``,
    # delta-rule linear attention (KDA, ops/kda.py) in most layers with
    # its recurrent state in a slot of the cache manager, latent
    # attention in the rest with its rows in the paged pool.  Layer i
    # of THIS model is published layer ``layer_offset + i``, and a
    # published layer j is MLA where (j + 1) % layer_group_size == 0,
    # else KDA with n_heads heads of head_dim keys and values.
    layer_group_size: int = 0
    layer_offset: int = 0
    kda_conv_size: int = 4       # causal depthwise convolution on q, k, v
    kda_lower_bound: float = -5.0  # log decay = bound x sigmoid(.)
    # ---- the MHA family widened, served only where any of these is
    # set (Command A+'s block).  Grouped-query heads: ``n_kv_heads`` K/V
    # heads (0: n_heads), query head h reads K/V head h // (n_heads /
    # n_kv_heads).  ``sliding_window`` W > 0: published layer j
    # (``layer_offset + i``) is "full" where (j + 1) % layer_group_size
    # == 0, else "sliding": query i sees keys i - W < j <= i, and its
    # K/V live in a pool and a block table of their own.  With
    # ``moe_router="sigmoid"`` the FFN is the held-expert layer above.
    n_kv_heads: int = 0
    sliding_window: int = 0
    full_layers_rope: bool = True   # False: full layers carry no positions
    norm: str = "rms"               # "layer": LayerNorm, a weight, no bias
    norm_eps: float = 1e-6
    parallel_block: bool = False    # x + Attn(xn) + FFN(xn), one norm a layer
    tie_embeddings: bool = False    # logits = logit_scale * x embed^T
    logit_scale: float = 1.0
    moe_shared_average: bool = False  # the shared experts' mean, not sum
    # the residual stream's type where it is not ``dtype`` ("float32"
    # under bf16 weights: every addend joins the stream as its matmul
    # accumulated it, the norms and the router read it unrounded, and
    # the logits come out in it; the matmuls' operands stay ``dtype``)
    residual_dtype: str = ""
    # ---- each kind of attention layer with a geometry of its own
    # (MiMo-V2's block).  ``attn_pattern``: published layer j is full
    # where its letter is "F", sliding where it is "S" (empty: the
    # layer_group_size rule above).  A sliding layer has
    # ``swa_n_kv_heads`` K/V heads (0: kv_heads), rotates by
    # ``swa_rope_theta`` (0: rope_theta) and, with ``swa_sink``, adds a
    # learned per-head logit to its softmax's denominator.  RoPE turns
    # the first ``rope_dim`` dims of a head (0: all of them), and the
    # attention's output is scaled by ``attn_value_scale`` before W_o
    attn_pattern: str = ""
    swa_n_kv_heads: int = 0
    swa_rope_theta: float = 0.0
    swa_sink: bool = False
    rope_dim: int = 0
    attn_value_scale: float = 1.0
    # ---- a fifth block family, served only: ``attention="nemotron_h"``
    # (Nemotron-H's block).  Every layer is ONE thing under one norm,
    # named by its letter of ``layer_pattern`` (the published
    # ``hybrid_override_pattern``, cut): "M" a Mamba-2 mixer
    # (ops/mamba2.py) whose state lives in a slot of the cache manager,
    # "*" grouped-query attention without positions whose K/V live in
    # the paged pool, "E" the held-expert layer.  Mamba-2: mamba_n_heads
    # heads of mamba_head_dim channels, B and C of mamba_state values
    # for each of mamba_n_groups groups of heads, a causal depthwise
    # convolution of mamba_conv_size taps (with bias) over x, B and C.
    layer_pattern: str = ""
    mamba_n_heads: int = 0
    mamba_head_dim: int = 0
    mamba_n_groups: int = 1
    mamba_state: int = 0
    mamba_conv_size: int = 4
    mamba_chunk: int = 128       # tokens a chunk of the prefill scan
    # the held experts in a latent: the token goes down to ``moe_latent``
    # once, gather, products, weighing and scatter-add run there, the
    # sum goes up once (0: the experts work at d_model).  ``moe_act``
    # "relu2": an expert is W2 relu(W1 u)^2, two matrices and no gate,
    # and so is the shared expert, ``moe_shared_d_ff`` wide (0:
    # moe_n_shared x moe_d_ff)
    moe_latent: int = 0
    moe_act: str = "swiglu"
    moe_shared_d_ff: int = 0
    # ---- the MHA family looped, served only (Ouro's LoopLM block): the
    # same ``n_layers`` run ``loop_passes`` times a token, each pass
    # closed by the final norm, whose output starts the next; layer l
    # of pass t keeps its K/V in cache layer ``t * n_layers + l`` of
    # the pools.  ``sandwich_norm``: each sublayer's output is normed
    # (``ln1_post``, ``ln2_post``) before it joins the residual stream
    loop_passes: int = 1
    sandwich_norm: bool = False

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def stream_dtype(self):
        return jnp.dtype(self.residual_dtype or self.dtype)

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def family(self) -> str:
        """Which pair of serving programs runs the model: the
        ``attention`` kind, and "mha_swa" for an MHA model whose
        sliding layers have pools and tables of their own."""
        if self.attention == "mha" and self.sliding_window:
            return "mha_swa"
        return self.attention

    @property
    def served_only(self) -> bool:
        """Whether the model has a serving path alone: no train step,
        sharding specs or backward kernels."""
        return (self.latent or self.moe_router == "sigmoid"
                or bool(self.n_kv_heads or self.sliding_window)
                or self.norm != "rms" or self.parallel_block
                or self.tie_embeddings or bool(self.residual_dtype)
                or self.looped)

    @property
    def looped(self) -> bool:
        """Whether the tree is :func:`_init_looped_params`'s: layers
        run more than once a token, or with sandwich norms."""
        return self.loop_passes > 1 or self.sandwich_norm

    @property
    def hybrid(self) -> bool:
        return self.attention == "kda_mla"

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_head_dim

    @property
    def mamba_conv_dim(self) -> int:
        """Channels the convolution runs over: x, B and C side by side."""
        return self.mamba_d_inner + 2 * self.mamba_n_groups * self.mamba_state

    @property
    def latent(self) -> bool:
        """Whether the paged pool holds latent rows (and the family is
        served only): all layers of "mla", the MLA layers of "kda_mla"."""
        return self.attention in ("mla", "kda_mla")

    @property
    def layer_kinds(self) -> tuple:
        """Each layer's attention, in order; under ``layer_pattern``
        what each layer IS ("mamba", "full" attention or "moe")."""
        if self.layer_pattern:
            return tuple({"M": "mamba", "*": "full", "E": "moe"}[c]
                         for c in self.layer_pattern[:self.n_layers])
        if self.attn_pattern:
            return tuple({"F": "full", "S": "sliding"}[c] for c in
                         self.attn_pattern[self.layer_offset:][:self.n_layers])
        if self.hybrid:
            every, rest = "mla", "kda"
        elif self.family == "mha_swa":
            every, rest = "full", "sliding"
        else:
            return (self.attention,) * self.n_layers
        return tuple(
            every if (self.layer_offset + i + 1) % self.layer_group_size == 0
            else rest for i in range(self.n_layers))

    def kv_pool_shapes(self, n_blocks: int, block_size: int) -> tuple:
        """Shapes of the paged cache's pools: K and V pages of
        ``[block_size, H, D]``, or under latent attention ONE pool
        whose page is ``[row, block_size]``: per token the normed K/V
        latent beside the RoPE'd key all heads share, with the slots as
        the minor axis (a page is then K^T as the score product takes
        it, 576 x 128 tiles without padding, and the array's default
        device layout is the one the kernel reads: with the row minor
        the chip's compiler laid the slots minor anyway and copied the
        whole pool into and out of every program)."""
        if self.latent:
            row = self.kv_lora_rank + self.qk_rope_head_dim
            return ((self.layer_kinds.count("mla"), n_blocks, row,
                     block_size),)
        # a sliding layer's K/V are in sliding_pool_shapes' pools, under
        # a layer pattern the attention layers alone have K/V, and each
        # pass of a looped model keeps its own
        n_layers = sum(kind in ("mha", "full") for kind in self.layer_kinds)
        return self._kv_shapes(
            (self.loop_passes * n_layers, n_blocks, block_size), "full")

    def sliding_pool_shapes(self, n_rows: int, block_size: int) -> tuple:
        """Shapes of the sliding layers' K and V pools, none for a
        model without such layers: pages as :meth:`kv_pool_shapes`, and
        as many blocks as ``n_rows`` live sequences can hold, a ring of
        ``ceil(sliding_window / block_size) + 1`` each (the block the
        window's oldest key lies in, those up to the newest, and no
        more however long the context: serving/kv_cache.py)."""
        n_sliding = self.layer_kinds.count("sliding")
        if not n_sliding:
            return ()
        ring = -(-self.sliding_window // block_size) + 1
        return self._kv_shapes((n_sliding, n_rows * ring, block_size),
                               "sliding")

    @property
    def flat_pages(self) -> bool:
        """Whether an MHA model's K and V pages lie ``[block_size, H_kv
        x width]``, the heads side by side in the lanes: where values
        have a width of their own.  A page ``[block_size, H_kv, 192]``
        would be laid out with the slots minor (192 is no whole number
        of 128-lane tiles) and copied whole for the kernel, which reads
        a page as rows of whole lanes."""
        return bool(self.v_head_dim) and not self.latent

    def kv_geometry(self, kind: str) -> tuple:
        """``(K/V heads, key width, value width)`` of an attention layer
        of this kind."""
        heads = self.swa_n_kv_heads if kind == "sliding" else 0
        return (heads or self.kv_heads, self.head_dim,
                self.v_head_dim or self.head_dim)

    def _kv_shapes(self, lead: tuple, kind: str) -> tuple:
        """The K and V pools of ``lead`` (layers, blocks, slots) for
        layers of this kind."""
        h_kv, dk, dv = self.kv_geometry(kind)
        if self.flat_pages:
            return lead + (h_kv * dk,), lead + (h_kv * dv,)
        return lead + (h_kv, dk), lead + (h_kv, dv)

    def state_slot_shapes(self, n_slots: int) -> tuple:
        """``(shape, dtype)`` of each array of per-sequence recurrent
        state the cache manager keeps beside the pools, a sequence's
        own at one slot of axis 1; none for a model without recurrent
        layers.  KDA: the float32 state S^T ``[H, d_v, d_k]`` per layer
        and slot, and the last ``kda_conv_size - 1`` inputs of the
        short convolution (q, k, v side by side, as projected), a
        slot's ``[W - 1, 3 H d]`` laid out as whole 128-lane rows where
        they divide it: a slot is then whole tiles and the decode
        program's write of the live rows' slots is in place (with 3
        rows a slot the chip's compiler re-tiled the whole array into
        and out of every step).  Mamba-2: the float32 state ``[H, P,
        N]`` per layer and slot, and the convolution's last
        ``mamba_conv_size - 1`` inputs (x, B, C side by side, as
        projected) in whole 128-lane rows for the same reason."""
        n_mamba = self.layer_kinds.count("mamba")
        if n_mamba:
            tail = (self.mamba_conv_size - 1) * self.mamba_conv_dim
            lanes = 128 if tail % 128 == 0 else tail
            return (((n_mamba, n_slots, self.mamba_n_heads,
                      self.mamba_head_dim, self.mamba_state), "float32"),
                    ((n_mamba, n_slots, tail // lanes, lanes), self.dtype))
        n_kda = self.layer_kinds.count("kda")
        if not n_kda:
            return ()
        h, d = self.n_heads, self.head_dim
        tail = (self.kda_conv_size - 1) * 3 * h * d
        lanes = 128 if tail % 128 == 0 else tail
        return (((n_kda, n_slots, h, d, d), "float32"),
                ((n_kda, n_slots, tail // lanes, lanes), self.dtype))


def flagship_config() -> TransformerConfig:
    """The single-chip benchmark model: ~1.0B-param dense decoder LM,
    bf16 + per-block remat, head_dim 128 to ride the Pallas flash kernel.
    Sized so a full AdamW train step fits a 16 GB-HBM chip (v5e)."""
    return TransformerConfig(
        vocab=32768,
        d_model=2048,
        n_heads=16,
        head_dim=128,
        d_ff=6144,
        n_layers=16,
        n_experts=1,
        microbatches=1,
        dtype="bfloat16",
        remat=True,
    )


def count_params(cfg: TransformerConfig) -> int:
    """Total parameter count of init_params' pytree."""
    if cfg.served_only:
        return sum(int(a.size) for a in jax.tree.leaves(
            jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))))
    e, hd, f, x = (cfg.d_model, cfg.n_heads * cfg.head_dim, cfg.d_ff,
                   cfg.n_experts)
    per_layer = 2 * e + 4 * e * hd + e * x + 3 * x * e * f
    return cfg.n_layers * per_layer + 2 * cfg.vocab * e + e


def _latent_forward_flops(cfg: TransformerConfig, t: int,
                          causal: bool) -> float:
    """Forward FLOPs one token needs under the latent block at context
    ``t``: the MLA projections (the query's through its latent or
    direct), scores at qk and values at v width, the dense or the
    held-expert FFN (a token's k picks land here in the held share of
    the router's outputs), the unembed.  A KDA layer of the hybrid
    family instead: its five full projections, beta and the gate, and
    about 6 operations a state element whatever the context."""
    e, h = cfg.d_model, cfg.n_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    q_proj = (e * cfg.q_lora_rank + cfg.q_lora_rank * h * qk
              if cfg.q_lora_rank else e * h * qk)
    mla = (q_proj + e * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
           + cfg.kv_lora_rank * h * (cfg.qk_nope_head_dim + cfg.v_head_dim)
           + h * cfg.v_head_dim * e)
    attn = (1 if causal else 2) * t * h * (qk + cfg.v_head_dim)
    kda = 2 * (5 * e * h * cfg.head_dim + 2 * e * h) \
        + 6 * h * cfg.head_dim * cfg.head_dim
    routed = cfg.moe_n_routed or cfg.n_experts
    expert = 3 * e * cfg.moe_d_ff * (
        cfg.moe_topk * cfg.n_experts / routed + cfg.moe_n_shared) + e * routed
    n_moe = cfg.n_layers - cfg.n_dense_layers
    kinds = cfg.layer_kinds
    return (kinds.count("mla") * (2 * mla + attn) + kinds.count("kda") * kda
            + cfg.n_dense_layers * 2 * 3 * e * cfg.d_ff
            + n_moe * 2 * expert + 2 * e * cfg.vocab)


def _gqa_forward_flops(cfg: TransformerConfig, t: int,
                       causal: bool) -> float:
    """Forward FLOPs one token needs under the widened MHA block at
    context ``t``: the q and o projections at n_heads and k, v at the
    K/V heads, scores and values over the keys each layer kind sees (a
    sliding layer at most its window), the FFN (dense, or the held
    share of the routed experts beside the shared ones and the router),
    the unembed; the layers as many times as a looped model runs them.
    Each kind of layer at its own K/V heads and widths, the leading
    dense layers at d_ff."""
    e, h = cfg.d_model, cfg.n_heads
    if cfg.moe_router == "sigmoid":
        routed = cfg.moe_n_routed or cfg.n_experts
        ffn = 2 * (3 * e * cfg.moe_d_ff * (
            cfg.moe_topk * cfg.n_experts / routed + cfg.moe_n_shared)
            + e * routed)
    else:
        ffn = 2 * 3 * e * cfg.d_ff * cfg.n_experts
    total = 0.0
    for i, kind in enumerate(cfg.layer_kinds):
        h_kv, dk, dv = cfg.kv_geometry(kind)
        seen = min(t, cfg.sliding_window) if kind == "sliding" else t
        total += (2 * e * (h + h_kv) * (dk + dv)
                  + (1 if causal and seen == t else 2) * seen * h * (dk + dv)
                  + (2 * 3 * e * cfg.d_ff if i < cfg.n_dense_layers
                     else ffn))
    return cfg.loop_passes * total + 2 * e * cfg.vocab


def _pattern_forward_flops(cfg: TransformerConfig, t: int,
                           causal: bool) -> float:
    """Forward FLOPs one token needs under a layer pattern at context
    ``t``: a Mamba-2 layer's two projections, its convolution and about
    5 operations a state element whatever the context; an attention
    layer's projections at its heads and K/V heads and its scores and
    values over the keys; an expert layer's router, the two latent
    projections, the held share of the picked two-matrix experts in the
    latent and the shared expert; the unembed."""
    e, d = cfg.d_model, cfg.head_dim
    inner, conv = cfg.mamba_d_inner, cfg.mamba_conv_dim
    mamba = (2 * e * (inner + conv + cfg.mamba_n_heads) + 2 * inner * e
             + 2 * cfg.mamba_conv_size * conv + 5 * inner * cfg.mamba_state)
    attn = (2 * e * d * (2 * cfg.n_heads + 2 * cfg.kv_heads)
            + (2 if causal else 4) * t * cfg.n_heads * d)
    routed = cfg.moe_n_routed or cfg.n_experts
    width = cfg.moe_latent or e
    mats = 2 if cfg.moe_act == "relu2" else 3
    shared = cfg.moe_shared_d_ff or cfg.moe_n_shared * cfg.moe_d_ff
    moe = 2 * (e * routed + (2 * e * width if cfg.moe_latent else 0)
               + mats * width * cfg.moe_d_ff * cfg.moe_topk
               * cfg.n_experts / routed + mats * e * shared)
    kinds = cfg.layer_kinds
    return (kinds.count("mamba") * mamba + kinds.count("full") * attn
            + kinds.count("moe") * moe + 2 * e * cfg.vocab)


def train_flops_per_token(cfg: TransformerConfig, t: int,
                          causal: bool = True) -> float:
    """Executed matmul FLOPs per token for one train step (fwd + bwd ≈ 3×
    fwd): qkvo + FFN + unembed projections plus the attention score/value
    matmuls.  With ``causal`` the attention term is halved — the flash
    kernels skip fully-masked KV blocks, so full-T counting would inflate
    MFU (conservative: the partially-masked diagonal blocks run full)."""
    if cfg.latent:
        return 3.0 * _latent_forward_flops(cfg, t, causal)
    if cfg.layer_pattern:
        return 3.0 * _pattern_forward_flops(cfg, t, causal)
    if cfg.served_only:
        return 3.0 * _gqa_forward_flops(cfg, t, causal)
    e, hd, f, x = (cfg.d_model, cfg.n_heads * cfg.head_dim, cfg.d_ff,
                   cfg.n_experts)
    attn = (2 if causal else 4) * t * hd
    per_layer = 2 * 4 * e * hd + attn + 2 * 3 * e * f * x
    fwd = cfg.n_layers * per_layer + 2 * e * cfg.vocab
    return 3.0 * fwd


def train_step_flops(cfg: TransformerConfig, batch: int, t: int,
                     causal: bool = True) -> float:
    """Executed FLOPs for ONE train step of a [batch, t] input — the
    model's declaration to the step ledger (telemetry.steps), from
    which per-step MFU = flops / wall / peak is accounted."""
    return train_flops_per_token(cfg, t, causal) * batch * t


def init_params(key, cfg: TransformerConfig, n_stages: int = 1):
    """Global (unsharded) parameter pytree; blocks stacked [S, L/S, ...]:
    the form the train step scans and shards.  The serving engine holds
    an MHA model's weights as :func:`per_layer_params` makes them of
    this tree, one array a layer and matrix."""
    if cfg.hybrid:
        return _init_hybrid_params(key, cfg, n_stages)
    if cfg.layer_pattern:
        return _init_pattern_params(key, cfg, n_stages)
    if cfg.latent:
        return _init_latent_params(key, cfg, n_stages)
    if cfg.moe_router == "sigmoid":
        return _init_held_expert_params(key, cfg, n_stages)
    if cfg.looped:
        return _init_looped_params(key, cfg, n_stages)
    assert cfg.n_layers % n_stages == 0
    lps = cfg.n_layers // n_stages
    e, h, d, f, x = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff, cfg.n_experts
    h_kv = cfg.kv_heads
    keys = iter(jax.random.split(key, 16))

    def norm(k, shape, scale=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(cfg.jdtype)

    blk = {
        "ln1": jnp.ones((n_stages, lps, e), cfg.jdtype),
        "ln2": jnp.ones((n_stages, lps, e), cfg.jdtype),
        "wq": norm(next(keys), (n_stages, lps, e, h, d)),
        "wk": norm(next(keys), (n_stages, lps, e, h_kv, d)),
        "wv": norm(next(keys), (n_stages, lps, e, h_kv, d)),
        "wo": norm(next(keys), (n_stages, lps, h, d, e)),
        "gate": norm(next(keys), (n_stages, lps, e, x)),
        "w_in": norm(next(keys), (n_stages, lps, x, e, f)),
        "w_gate": norm(next(keys), (n_stages, lps, x, e, f)),
        "w_out": norm(next(keys), (n_stages, lps, x, f, e)),
    }
    if cfg.parallel_block:
        del blk["ln2"]
    tree = {
        "embed": norm(next(keys), (cfg.vocab, e)),
        "unembed": norm(next(keys), (e, cfg.vocab)),
        "ln_f": jnp.ones((e,), cfg.jdtype),
        "blocks": blk,
    }
    if cfg.tie_embeddings:
        del tree["unembed"]
    return tree


def _init_held_expert_params(key, cfg: TransformerConfig, n_stages: int = 1):
    """The widened MHA family with held experts (``moe_router=
    "sigmoid"``), served only: ``layers`` is a LIST of one dict a layer
    (``ln1``, ``ln2`` unless the block is parallel, ``wq [E, H, d]``,
    ``wk`` / ``wv [E, H_kv, d]``, ``wo [H, d, E]``, the router ``gate
    [E, moe_n_routed]`` and the shared experts side by side ``s_in`` /
    ``s_gate [E, n_shared x F]``, ``s_out [n_shared x F, E]``), an array
    a layer and matrix because a decode step would copy its layer's
    slice out of a stack (689 MB a layer at Command A+'s widths); the
    held routed experts of ALL layers stay one stack ``experts``
    (``w_in`` / ``w_gate [L x X, E, F]``, ``w_out [L x X, F, E]``, layer
    i's at [i X, (i + 1) X)), which is how the grouped product takes
    them (:func:`_moe_held_ffn`).  ``unembed`` is absent where the head
    is tied to ``embed``.

    Each layer's K/V heads and value width are its kind's
    (:meth:`TransformerConfig.kv_geometry`: ``wv [E, H_kv, d_v]``,
    ``wo [H, d_v, E]``).  A leading dense layer (i < n_dense_layers)
    has the d_ff SwiGLU ``w_in`` / ``w_gate [E, d_ff]``, ``w_out [d_ff,
    E]`` in place of the router, and no experts in the stack.  With
    ``moe_router_bias`` a router has its float32 correction bias
    ``gate_bias [routed]``; with ``swa_sink`` a sliding layer has its
    float32 ``sink [H]``, seeded uniform in [0, ln(2 x window)]: from
    under one key's weight to twice the whole window's."""
    assert n_stages == 1
    e, h, d = cfg.d_model, cfg.n_heads, cfg.head_dim
    x, fm, fs = cfg.n_experts, cfg.moe_d_ff, cfg.moe_n_shared * cfg.moe_d_ff
    routed = cfg.moe_n_routed or x
    n = cfg.n_layers
    n_moe = n - cfg.n_dense_layers
    keys = iter(jax.random.split(key, 8 * n + 8))

    def norm(*shape, dtype=cfg.jdtype):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * 0.02).astype(dtype)

    def ones():
        return jnp.ones((e,), cfg.jdtype)

    def layer(i, kind):
        h_kv, _, dv = cfg.kv_geometry(kind)
        p = {"ln1": ones(), "wq": norm(e, h, d), "wk": norm(e, h_kv, d),
             "wv": norm(e, h_kv, dv), "wo": norm(h, dv, e)}
        if i < cfg.n_dense_layers:
            p.update(w_in=norm(e, cfg.d_ff), w_gate=norm(e, cfg.d_ff),
                     w_out=norm(cfg.d_ff, e))
        else:
            p["gate"] = norm(e, routed)
            if cfg.moe_router_bias:
                p["gate_bias"] = norm(routed, dtype=jnp.float32)
        if kind == "sliding" and cfg.swa_sink:
            p["sink"] = jax.random.uniform(
                next(keys), (h,), jnp.float32, 0.0,
                math.log(2.0 * cfg.sliding_window))
        if not cfg.parallel_block:
            p["ln2"] = ones()
        if fs:
            p.update(s_in=norm(e, fs), s_gate=norm(e, fs), s_out=norm(fs, e))
        return p

    tree = {
        "embed": norm(cfg.vocab, e), "ln_f": ones(),
        "layers": [layer(i, kind) for i, kind in enumerate(cfg.layer_kinds)],
        "experts": {"w_in": norm(n_moe * x, e, fm),
                    "w_gate": norm(n_moe * x, e, fm),
                    "w_out": norm(n_moe * x, fm, e)},
    }
    if not cfg.tie_embeddings:
        tree["unembed"] = norm(e, cfg.vocab)
    return tree


def _init_looped_params(key, cfg: TransformerConfig, n_stages: int = 1):
    """The MHA family looped (``loop_passes``) or with sandwich norms,
    served only: ``layers`` is a LIST of one dict a layer from the
    start (``ln1``, ``ln1_post``, ``ln2``, ``ln2_post``, ``wq [H, E,
    d]`` and ``wk`` / ``wv [H_kv, E, d]`` head-major (:func:`_heads`),
    ``wo [H, d, E]`` and ONE dense SwiGLU ``w_in`` / ``w_gate [E, F]``,
    ``w_out [F, E]``, no router), so that the engine and the
    benchmark's reference read the same arrays and the weights are on
    the chip once.  ``w_exit [E]`` and
    ``b_exit [1]`` are the exit gate after each pass; the serving
    programs run every pass and do not compute it."""
    assert n_stages == 1 and cfg.n_experts == 1
    e, h, h_kv, d, f = (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim,
                        cfg.d_ff)
    keys = iter(jax.random.split(key, 7 * cfg.n_layers + 3))

    def norm(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * 0.02).astype(cfg.jdtype)

    def ones():
        return jnp.ones((e,), cfg.jdtype)

    def layer():
        p = {"ln1": ones(), "ln2": ones(), "wq": norm(h, e, d),
             "wk": norm(h_kv, e, d), "wv": norm(h_kv, e, d),
             "wo": norm(h, d, e), "w_in": norm(e, f), "w_gate": norm(e, f),
             "w_out": norm(f, e)}
        if cfg.sandwich_norm:
            p.update(ln1_post=ones(), ln2_post=ones())
        return p

    return {"embed": norm(cfg.vocab, e), "unembed": norm(e, cfg.vocab),
            "ln_f": ones(), "layers": [layer() for _ in range(cfg.n_layers)],
            "w_exit": norm(e), "b_exit": jnp.zeros((1,), jnp.float32)}


def _init_latent_params(key, cfg: TransformerConfig, n_stages: int = 1):
    """The latent family's tree: a leading-dense group ``dense``
    stacked [n_dense_layers, ...] beside the expert layers ``blocks``
    stacked [S, L/S, ...] as the MHA tree's are.  Both carry the MLA
    projections (w_qa, q_norm, w_qb, w_kva, kv_norm, w_kvb, wo);
    ``dense`` the d_ff SwiGLU, ``blocks`` the router ``gate`` over all
    moe_n_routed experts, the n_experts held routed experts and the
    shared expert(s) as one SwiGLU of moe_n_shared x moe_d_ff."""
    n_moe = cfg.n_layers - cfg.n_dense_layers
    assert n_moe % n_stages == 0 and cfg.moe_router == "sigmoid"
    e, h, x = cfg.d_model, cfg.n_heads, cfg.n_experts
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, pe, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    fm, fs = cfg.moe_d_ff, cfg.moe_n_shared * cfg.moe_d_ff
    routed = cfg.moe_n_routed or x
    keys = iter(jax.random.split(key, 32))

    def norm(shape, scale=0.02):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(cfg.jdtype)

    def group(lead, ffn):
        return {
            "ln1": jnp.ones(lead + (e,), cfg.jdtype),
            "ln2": jnp.ones(lead + (e,), cfg.jdtype),
            "w_qa": norm(lead + (e, rq)),
            "q_norm": jnp.ones(lead + (rq,), cfg.jdtype),
            "w_qb": norm(lead + (rq, h, nope + pe)),
            "w_kva": norm(lead + (e, rkv + pe)),
            "kv_norm": jnp.ones(lead + (rkv,), cfg.jdtype),
            "w_kvb": norm(lead + (rkv, h, nope + dv)),
            "wo": norm(lead + (h, dv, e)),
            **{name: norm(lead + shape) for name, shape in ffn.items()},
        }

    return {
        "embed": norm((cfg.vocab, e)),
        "unembed": norm((e, cfg.vocab)),
        "ln_f": jnp.ones((e,), cfg.jdtype),
        "dense": group((cfg.n_dense_layers,), {
            "w_in": (e, cfg.d_ff), "w_gate": (e, cfg.d_ff),
            "w_out": (cfg.d_ff, e)}),
        "blocks": group((n_stages, n_moe // n_stages), {
            "gate": (e, routed),
            "w_in": (x, e, fm), "w_gate": (x, e, fm), "w_out": (x, fm, e),
            "s_in": (e, fs), "s_gate": (e, fs), "s_out": (fs, e)}),
    }


def _init_hybrid_params(key, cfg: TransformerConfig, n_stages: int = 1):
    """The hybrid family's tree: attention and FFN in groups of their
    own, because a layer's two halves vary independently.  ``kda``
    stacked [n KDA layers, ...] and ``mla`` [n MLA layers, ...] hold
    ``ln1`` and the attention weights, each in layer order; ``dense``
    [n_dense_layers, ...] and ``blocks`` [1, n expert layers, ...] hold
    ``ln2`` and the FFN as the latent tree's do, the router with its
    float32 correction bias ``gate_bias``.  KDA: ``w_qkv`` (q, k, v
    side by side), the convolution ``conv [width, 3 H d]``, the decay's
    full-rank projection ``w_a`` with ``a_log [H]`` and ``dt_bias
    [H, d]`` in float32 (seeded so that the decay spans its range:
    exp(a_log) uniform in [0.5, 4], dt_bias uniform in [-3, 3]),
    ``w_beta``, the head-wise output gate ``w_og``, the per-head output
    norm ``o_norm`` and ``wo``.  MLA: the query projected directly
    (``w_q``), then as the latent tree."""
    kinds = cfg.layer_kinds
    n_kda, n_mla = kinds.count("kda"), kinds.count("mla")
    n_moe = cfg.n_layers - cfg.n_dense_layers
    assert n_stages == 1 and cfg.moe_router == "sigmoid" \
        and cfg.q_lora_rank == 0
    e, h, d, x = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.n_experts
    rkv = cfg.kv_lora_rank
    nope, pe, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    fm, fs = cfg.moe_d_ff, cfg.moe_n_shared * cfg.moe_d_ff
    routed = cfg.moe_n_routed or x
    keys = iter(jax.random.split(key, 40))

    def norm(shape, dtype=cfg.jdtype):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * 0.02).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    def ones(*shape):
        return jnp.ones(shape, cfg.jdtype)

    blocks = {
        "ln2": ones(1, n_moe, e), "gate": norm((1, n_moe, e, routed)),
        "w_in": norm((1, n_moe, x, e, fm)),
        "w_gate": norm((1, n_moe, x, e, fm)),
        "w_out": norm((1, n_moe, x, fm, e)),
        "s_in": norm((1, n_moe, e, fs)), "s_gate": norm((1, n_moe, e, fs)),
        "s_out": norm((1, n_moe, fs, e))}
    if cfg.moe_router_bias:
        blocks["gate_bias"] = norm((1, n_moe, routed), jnp.float32)
    return {
        "embed": norm((cfg.vocab, e)),
        "unembed": norm((e, cfg.vocab)),
        "ln_f": ones(e),
        "kda": {
            "ln1": ones(n_kda, e),
            "w_qkv": norm((n_kda, e, 3 * h * d)),
            "conv": norm((n_kda, cfg.kda_conv_size, 3 * h * d)),
            "w_a": norm((n_kda, e, h * d)),
            "a_log": jnp.log(uniform((n_kda, h), 0.5, 4.0)),
            "dt_bias": uniform((n_kda, h, d), -3.0, 3.0),
            "w_beta": norm((n_kda, e, h)),
            "w_og": norm((n_kda, e, h)),
            "o_norm": ones(n_kda, d),
            "wo": norm((n_kda, h, d, e))},
        "mla": {
            "ln1": ones(n_mla, e),
            "w_q": norm((n_mla, e, h, nope + pe)),
            "w_kva": norm((n_mla, e, rkv + pe)),
            "kv_norm": ones(n_mla, rkv),
            "w_kvb": norm((n_mla, rkv, h, nope + dv)),
            "wo": norm((n_mla, h, dv, e))},
        "dense": {
            "ln2": ones(cfg.n_dense_layers, e),
            "w_in": norm((cfg.n_dense_layers, e, cfg.d_ff)),
            "w_gate": norm((cfg.n_dense_layers, e, cfg.d_ff)),
            "w_out": norm((cfg.n_dense_layers, cfg.d_ff, e))},
        "blocks": blocks,
    }


def _init_pattern_params(key, cfg: TransformerConfig, n_stages: int = 1):
    """The tree of a model under a layer pattern (``attention=
    "nemotron_h"``), served only: ``layers`` is a LIST of one dict a
    layer, each with its one norm ``ln`` and what its letter says, an
    array a layer and matrix (a decode step would copy its layer's
    slice out of a stack, 152 MB for a Mamba-2 input projection).

    "M": ``in_proj [E, d_inner + conv_dim + H]`` (z, xBC and dt side by
    side), the convolution ``conv [width, conv_dim]`` with its bias
    ``conv_b``, and in float32 ``dt_bias [H]`` (seeded so that
    softplus(dt_bias) is log-uniform in [0.001, 0.1]), ``a_log [H]`` =
    log(uniform[1, 16]) and the skip ``d [H]`` = 1; the gated norm's
    weight ``norm [d_inner]`` and ``out_proj [d_inner, E]``.
    "*": ``wq [E, H, d]``, ``wk`` / ``wv [E, H_kv, d]``, ``wo [H, d, E]``.
    "E": the router ``gate [E, moe_n_routed]`` with its float32
    correction bias ``gate_bias``, the latent projections ``w_down [E,
    latent]`` / ``w_up [latent, E]`` and the shared expert ``s_in [E,
    F_s]`` / ``s_out [F_s, E]``.  The held routed experts of ALL expert
    layers stay one stack ``experts`` (``w_in [n_E x X, latent, F]``,
    ``w_out [n_E x X, F, latent]``, the j-th expert layer's at [j X,
    (j + 1) X)), which is how the grouped product takes them
    (:func:`_moe_held_ffn`)."""
    assert n_stages == 1 and cfg.moe_router == "sigmoid" \
        and cfg.moe_act == "relu2" and len(cfg.layer_pattern) >= cfg.n_layers
    e, h, h_kv, d = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    x, fm = cfg.n_experts, cfg.moe_d_ff
    fs = cfg.moe_shared_d_ff or cfg.moe_n_shared * fm
    width = cfg.moe_latent or e
    routed = cfg.moe_n_routed or x
    inner, conv, mh = cfg.mamba_d_inner, cfg.mamba_conv_dim, cfg.mamba_n_heads
    kinds = cfg.layer_kinds
    keys = iter(jax.random.split(key, 8 * cfg.n_layers + 8))

    def norm(*shape, dtype=cfg.jdtype):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * 0.02).astype(dtype)

    def uniform(lo, hi):
        return jax.random.uniform(next(keys), (mh,), jnp.float32, lo, hi)

    def ones(n):
        return jnp.ones((n,), cfg.jdtype)

    def layer(kind):
        if kind == "mamba":
            dt = jnp.exp(uniform(jnp.log(0.001), jnp.log(0.1)))
            return {"ln": ones(e), "in_proj": norm(e, inner + conv + mh),
                    "conv": norm(cfg.mamba_conv_size, conv),
                    "conv_b": norm(conv),
                    # softplus^-1(dt)
                    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                    "a_log": jnp.log(uniform(1.0, 16.0)),
                    "d": jnp.ones((mh,), jnp.float32),
                    "norm": ones(inner), "out_proj": norm(inner, e)}
        if kind == "full":
            return {"ln": ones(e), "wq": norm(e, h, d),
                    "wk": norm(e, h_kv, d), "wv": norm(e, h_kv, d),
                    "wo": norm(h, d, e)}
        p = {"ln": ones(e), "gate": norm(e, routed), "s_in": norm(e, fs),
             "s_out": norm(fs, e)}
        if cfg.moe_router_bias:
            p["gate_bias"] = norm(routed, dtype=jnp.float32)
        if cfg.moe_latent:
            p.update(w_down=norm(e, width), w_up=norm(width, e))
        return p

    n_moe = kinds.count("moe")
    return {
        "embed": norm(cfg.vocab, e), "unembed": norm(e, cfg.vocab),
        "ln_f": ones(e), "layers": [layer(kind) for kind in kinds],
        "experts": {"w_in": norm(n_moe * x, width, fm),
                    "w_out": norm(n_moe * x, fm, width)},
    }


def param_specs():
    """PartitionSpecs matching init_params' pytree structure."""
    blk = {
        "ln1": P(AXIS_PP),
        "ln2": P(AXIS_PP),
        "wq": P(AXIS_PP, None, None, AXIS_TP, None),
        "wk": P(AXIS_PP, None, None, AXIS_TP, None),
        "wv": P(AXIS_PP, None, None, AXIS_TP, None),
        "wo": P(AXIS_PP, None, AXIS_TP, None, None),
        "gate": P(AXIS_PP),
        "w_in": P(AXIS_PP, None, AXIS_EP, None, AXIS_TP),
        "w_gate": P(AXIS_PP, None, AXIS_EP, None, AXIS_TP),
        "w_out": P(AXIS_PP, None, AXIS_EP, AXIS_TP, None),
    }
    return {
        "embed": P(AXIS_TP, None),
        "unembed": P(None, AXIS_TP),
        "ln_f": P(),
        "blocks": blk,
    }


def _attention(x, p, positions, axes: ShardAxes):
    """Multi-head attention; heads tp-sharded, sequence sp-sharded."""
    q = jnp.einsum("bte,ehd->bthd", x, p["wq"])
    k = jnp.einsum("bte,ehd->bthd", x, p["wk"])
    v = jnp.einsum("bte,ehd->bthd", x, p["wv"])
    q = rope(q, positions)
    k = rope(k, positions)
    if axes.sp is not None:
        o = ring_attention(q, k, v, axis_name=axes.sp, causal=True)
    else:
        o = _causal_attention(q, k, v)
    y = jnp.einsum("bthd,hde->bte", o, p["wo"])
    if axes.tp is not None:
        y = lax.psum(y, axes.tp)
    return y


def _moe_dense_ffn(x, p, axes: ShardAxes):
    """Soft-gated MoE; experts sharded over (ep, tp), combined in one psum.

    Exact (every expert sees every token) — the correctness oracle for
    the routed path and the default for small expert counts."""
    n_local = p["w_in"].shape[0]
    gate_logits = jnp.einsum("bte,ex->btx", x, p["gate"])  # [B,T,X_global]
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    if axes.ep is not None:
        off = lax.axis_index(axes.ep) * n_local
        local_probs = lax.dynamic_slice_in_dim(probs, off, n_local, axis=-1)
    else:
        local_probs = probs

    def one_expert(w_in, w_gate, w_out):
        return swiglu_ffn(x, w_in, w_gate, w_out, axes, reduce=False)

    ys = jax.vmap(one_expert)(p["w_in"], p["w_gate"], p["w_out"])  # [Xl,B,T,E]
    y = jnp.einsum("xbte,btx->bte", ys, local_probs.astype(ys.dtype))
    reduce_axes = tuple(a for a in (axes.ep, axes.tp) if a is not None)
    if reduce_axes:
        y = lax.psum(y, reduce_axes)
    return y


def _moe_topk_ffn(x, p, axes: ShardAxes, cfg: "TransformerConfig"):
    """Top-k routed MoE (Switch/GShard-style capacity dispatch).

    TPU-first: every shape is static.  Tokens are replicated across the
    ep axis (dp/sp own the token sharding), so routing is LOCAL: each
    shard scatters only the (token, choice) pairs whose expert it owns
    into a [X_local, capacity, E] buffer (capacity =
    ceil(k·n·capacity_factor / X_global); overflow tokens are dropped —
    the standard trade for static shapes), runs its expert FFNs, and the
    weighted combine psums over (ep, tp) — every choice contributes on
    exactly the shard owning its expert, so expert compute is k/X of the
    dense path and perfectly sharded with NO token exchange.
    """
    b, t, e = x.shape
    n = b * t
    k = cfg.moe_topk
    xf = x.reshape(n, e)
    gate_logits = jnp.einsum("ne,ex->nx", xf, p["gate"])
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    n_expert = probs.shape[-1]                       # X_global
    topv, topi = lax.top_k(probs, k)                 # [n, k]
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)

    x_l = p["w_in"].shape[0]                         # local experts
    off = (lax.axis_index(axes.ep) * x_l if axes.ep is not None else 0)
    capacity = -(-(k * n * cfg.moe_capacity_factor) // n_expert)
    capacity = max(int(capacity), 1)

    # local routing: (token, choice) pairs owned by this shard's experts
    flat_e = topi.reshape(-1)                        # [n·k], token-major
    local = (flat_e >= off) & (flat_e < off + x_l)
    le = jnp.clip(flat_e - off, 0, x_l - 1)
    # slot position within each local expert (capacity dispatch)
    oh = jax.nn.one_hot(le, x_l, dtype=jnp.int32) * local[:, None]
    pos = jnp.sum((jnp.cumsum(oh, axis=0) - 1) * oh, axis=-1)  # [n·k]
    keep = (local & (pos < capacity))
    pos_c = jnp.minimum(pos, capacity - 1)
    if cfg.moe_debug_overflow:
        # dropped-choice fraction on THIS shard: overflowed (token,
        # choice) pairs silently contribute residual only, so load
        # imbalance is invisible without this signal (metrics stage
        # "moe": overflow_fraction_sum / overflow_checks = mean rate)
        n_local_choices = jnp.sum(local.astype(jnp.float32))
        n_dropped = n_local_choices - jnp.sum(keep.astype(jnp.float32))
        jax.debug.callback(
            _record_moe_overflow,
            n_dropped / jnp.maximum(n_local_choices, 1.0))

    # dispatch: [X_local, C, E] — owned tokens scattered unweighted
    xk = jnp.repeat(xf, k, axis=0) * keep[:, None].astype(xf.dtype)
    buf = jnp.zeros((x_l, capacity, e), xf.dtype)
    buf = buf.at[le, pos_c].add(xk)

    def one_expert(w_in, w_gate, w_out, xe):
        return swiglu_ffn(xe, w_in, w_gate, w_out, axes, reduce=False)

    out = jax.vmap(one_expert)(p["w_in"], p["w_gate"], p["w_out"], buf)

    # combine: gather each owned (token, choice)'s output, weight, sum;
    # remote choices contribute on their owning shard via the psum
    picked = out[le, pos_c]                          # [n·k, E]
    w = (topv.reshape(-1) * keep.astype(jnp.float32)).astype(picked.dtype)
    y = jnp.sum((picked * w[:, None]).reshape(n, k, e), axis=1)
    y = y.reshape(b, t, e)
    reduce_axes = tuple(a for a in (axes.ep, axes.tp) if a is not None)
    if reduce_axes:
        y = lax.psum(y, reduce_axes)
    return y.astype(x.dtype)


def _record_moe_overflow(frac) -> None:
    from .. import metrics

    metrics.inc("moe", "overflow_checks")
    metrics.inc("moe", "overflow_fraction_sum", float(frac))


def _moe_ffn(x, p, axes: ShardAxes, cfg: "TransformerConfig"):
    if cfg.moe_topk > 0 and cfg.n_experts > 1:
        return _moe_topk_ffn(x, p, axes, cfg)
    return _moe_dense_ffn(x, p, axes)


def _block(x, p, positions, axes: ShardAxes, cfg: "TransformerConfig"):
    # named_scope labels are trace-time only (zero runtime cost); they
    # name the HLO so a profiler capture's reduction can attribute
    # device time to attention vs mlp
    with jax.named_scope("attention"):
        x = x + _attention(rms_norm(x, p["ln1"]), p, positions, axes)
    with jax.named_scope("mlp"):
        x = x + _moe_ffn(rms_norm(x, p["ln2"]), p, axes, cfg)
    return x


def _stage_fn(stage_params, x, positions, axes: ShardAxes,
              cfg: "TransformerConfig", remat: bool = False):
    """Apply this stage's L/S blocks via scan over the layer dim."""
    blk = _block
    if remat:
        # rematerialize each block on the backward pass: only the block
        # inputs (residual stream) are saved, so activation memory is
        # O(L·B·T·E) instead of O(L·B·T·(E+F+hd...)); the save_flash
        # policy additionally keeps the attention kernels' residuals
        if cfg.remat_policy == "save_flash":
            policy = jax.checkpoint_policies.save_only_these_names(
                "flash_o", "flash_lse")
        elif cfg.remat_policy == "save_flash_mlp":
            # + the MLP hidden activation: ~B*T*F bf16 per layer of HBM
            # buys back the block's largest recompute matmuls (in/gate)
            policy = jax.checkpoint_policies.save_only_these_names(
                "flash_o", "flash_lse", "mlp_act")
        elif cfg.remat_policy == "full":
            policy = None
        else:
            raise ValueError(
                f"unknown remat_policy {cfg.remat_policy!r}; "
                "expected 'full', 'save_flash', or 'save_flash_mlp'")
        blk = jax.checkpoint(_block, static_argnums=(3, 4), policy=policy)

    def body(h, layer_p):
        return blk(h, layer_p, positions, axes, cfg), None

    out, _ = lax.scan(body, x, stage_params)
    return out


def forward_local(params, ids, labels, cfg: TransformerConfig, axes: ShardAxes,
                  reduce_loss: bool = True):
    """Per-device loss.  ids/labels: [B_local, T_local] (dp × sp shards).

    Inside shard_map, `params` are the local shards; with ShardAxes()
    this is the unsharded oracle.  Returns scalar mean loss (f32),
    fully reduced over (dp, sp) when those axes are present.

    ``reduce_loss=False`` returns the LOCAL mean loss instead: the
    overlap train step differentiates that and issues the (dp, sp)
    gradient reduction itself as bucketed psums
    (parallel.overlap.bucketed_psum_mean) so XLA can hide the
    collectives under remaining backward compute — the pmean here
    would transpose into one fused gradient reduction at the very end
    of backward, fully exposed.
    """
    if cfg.served_only:
        raise NotImplementedError(
            "this block (latent attention, held experts, grouped heads, a "
            "sliding window, LayerNorm, a parallel block or a tied head) "
            "is served only: it has no train step, sharding specs or "
            "backward attention kernel yet")
    b, t_local = ids.shape
    sp_rank = lax.axis_index(axes.sp) if axes.sp is not None else 0
    positions = sp_rank * t_local + jnp.arange(t_local)

    x = embed_lookup(params["embed"], ids, axes).astype(cfg.jdtype)

    blocks = params["blocks"]
    if axes.pp is not None:
        stage_params = jax.tree.map(lambda a: a[0], blocks)  # local S=1
        m = cfg.microbatches
        assert b % m == 0, f"batch {b} must divide microbatches {m}"
        xmb = x.reshape(m, b // m, t_local, cfg.d_model)
        out = pipeline_spmd(
            lambda p_, h: _stage_fn(p_, h, positions, axes, cfg, cfg.remat),
            stage_params,
            xmb,
            axis_name=axes.pp,
        )
        x = out.reshape(b, t_local, cfg.d_model)
    else:
        n_stages = blocks["ln1"].shape[0]
        for s in range(n_stages):
            stage_params = jax.tree.map(lambda a: a[s], blocks)
            x = _stage_fn(stage_params, x, positions, axes, cfg, cfg.remat)

    x = rms_norm(x, params["ln_f"])
    logits = jnp.einsum("bte,ev->btv", x, params["unembed"])
    loss = softmax_xent(logits, labels, axes)  # [B, T_local]
    loss = jnp.mean(loss)
    reduce_axes = tuple(a for a in (axes.dp, axes.sp) if a is not None)
    if reduce_axes and reduce_loss:
        loss = lax.pmean(loss, reduce_axes)
    return loss


def unsharded_loss(params, ids, labels, cfg: TransformerConfig):
    """Single-device oracle (also the single-chip entry() forward)."""
    return forward_local(params, ids, labels, cfg, ShardAxes())


# ---------------------------------------------------------------------------
# serving forward paths: prefill (full sequence) and decode against the
# KV cache dmlc_tpu.serving owns (serving/kv_cache.py), whose
# device-resident pools the paged programs read and write in place (the
# one walk of every family: the end of this module)
# ---------------------------------------------------------------------------


def decode_flops_per_token(cfg: TransformerConfig, ctx: int) -> float:
    """Executed forward FLOPs for ONE generated token attending a
    ``ctx``-token context — the serving engine's declaration to the
    step ledger, so decode-step MFU is accounted on the same basis as
    training MFU.  A decode token runs every projection once and its
    attention reads the full context (no causal halving applies), which
    is exactly the forward third of ``train_flops_per_token`` counted
    without the causal discount."""
    return train_flops_per_token(cfg, ctx, causal=False) / 3.0


def _causal_attention(q, k, v, scale=None, span: int = 0, q_offset=None,
                      sink=None):
    """Causal full-sequence attention with the whole sequence on this
    device (serving prefill; training without an sp axis): the Pallas
    flash kernel — O(T) memory instead of a materialized [B,H,T,T]
    score matrix — or the lax oracle, as ops/dispatch decides.  ``v``
    may have its own head size (latent attention: qk 192, v 128), k
    and v fewer heads than q (grouped-query), ``span`` > 0 is a sliding
    window, ``q_offset`` places q's rows at ``q_offset + i`` against
    the keys and ``sink`` [H] is a per-head logit in the softmax's
    denominator (``ops.flash_attention.flash_attention``)."""
    from ..ops import dispatch
    from ..ops import flash_attention as _flash

    mode = dispatch.choose(_flash.supports(q.shape, k.shape, v.shape))
    plain = (k.shape[2] == q.shape[2] and not span and q_offset is None
             and sink is None)
    if mode == dispatch.LAX and plain:
        return ring_attention_reference(q, k, v, causal=True, scale=scale)
    if mode == dispatch.LAX:
        return _flash.lax_attention(
            q, k, v, scale=q.shape[-1] ** -0.5 if scale is None else scale,
            span=span, q_offset=q_offset, sink=sink)
    return _flash.flash_attention(q, k, v, causal=True, scale=scale,
                                  interpret=mode == dispatch.INTERPRET,
                                  span=span, q_offset=q_offset, sink=sink)


def _layer_params(blocks, stage: int, layer: int):
    return jax.tree.map(lambda a: a[stage, layer], blocks)


def per_layer_params(params):
    """The stacked MHA tree (``blocks`` with leading ``[S, L/S]``) as
    the serving programs read it: ``layers``, a list of one dict a
    layer, each matrix an array of its own, beside ``embed``,
    ``unembed`` and ``ln_f`` as they are.  The engine calls it once,
    outside any program; a program handed the stack makes the same
    slices inside itself (:func:`_serving_layers`), on every call, and XLA
    copies some of them (403 MB a decode step at the flagship's
    widths).  Any other tree (one that has ``layers`` already, the
    latent and hybrid families') is returned as the object it is."""
    blocks = params.get("blocks")
    if blocks is None or "wq" not in blocks:
        return params
    n_stages, lps = blocks["ln1"].shape[:2]
    tree = {k: v for k, v in params.items() if k != "blocks"}
    tree["layers"] = [_layer_params(blocks, s, i)
                      for s in range(n_stages) for i in range(lps)]
    return tree


#: rows of a prompt one pass of a prefill layer takes where the prompt
#: is longer: q, o and the FFN's hidden activations of 8k rows are a
#: quarter of a 32k prompt's, which beside 9.5 GB of weights would not
#: fit; K and V (67 MB a layer at 32k and 8 K/V heads) stay whole and
#: every chunk of rows attends them at its offset
PREFILL_ROWS = 8192


def _norm(x, scale, cfg: TransformerConfig):
    if cfg.norm == "layer":
        return layer_norm(x, scale, cfg.norm_eps)
    return rms_norm(x, scale, cfg.norm_eps)


def _into_stream(cfg: TransformerConfig) -> dict:
    """Keywords of a product whose result joins the residual stream:
    none where the stream has the operands' type (the flagship's
    programs lower as they always did)."""
    if cfg.stream_dtype == cfg.jdtype:
        return {}
    return {"preferred_element_type": cfg.stream_dtype}


def _rotates(kind: str, cfg: TransformerConfig) -> bool:
    """Whether a layer of this kind rotates q and k by position."""
    return kind != "full" or cfg.full_layers_rope


def _rotate(rotation, x, positions, kind: str, cfg: TransformerConfig):
    """``x [B, T, H, d]`` rotated by ``rotation`` (``rope`` or
    :func:`_rope_window`) at its kind's theta: the whole head, or its
    first ``rope_dim`` dims as two halves, the rest as it is."""
    theta = (cfg.swa_rope_theta if kind == "sliding" and cfg.swa_rope_theta
             else cfg.rope_theta)
    n = cfg.rope_dim
    if not n:
        return rotation(x, positions, theta)
    return jnp.concatenate([rotation(x[..., :n], positions, theta),
                            x[..., n:]], axis=-1)


def _attention_out(o, p, cfg: TransformerConfig):
    """The attention's addend: ``o [B, T, H, d_v]`` scaled by
    ``attn_value_scale`` (as scaling v would), then W_o."""
    if cfg.attn_value_scale != 1.0:
        o = o * jnp.asarray(cfg.attn_value_scale, o.dtype)
    return jnp.einsum("bthd,hde->bte", o, p["wo"], **_into_stream(cfg))


def _post_norm(y, p, name: str, cfg: TransformerConfig):
    """A sublayer's addend as it joins the stream: under sandwich norms
    normed by the layer's ``p[name]``, else as it is."""
    return _norm(y, p[name], cfg) if cfg.sandwich_norm else y


def _heads(xa, w, cfg: TransformerConfig):
    """``xa [B, T, E]`` projected to heads ``[B, T, H, d]`` by ``w``,
    which a looped model stores head-major ``[H, E, d]`` and every other
    tree ``[E, H, d]``: inside the pass loop the chip's compiler lays an
    ``[E, H, d]`` matrix out head-major anyway, in a copy of every
    layer's q, k and v matrices made before the loop on every call (1.2
    GB at Ouro-2.6B's widths, by the compile for a described v5e)."""
    if cfg.looped:
        return jnp.einsum("bte,hed->bthd", xa, w)
    return jnp.einsum("bte,ehd->bthd", xa, w)


def _scope(name):
    """``jax.named_scope(name)``, or nothing where there is no name."""
    return jax.named_scope(name) if name else contextlib.nullcontext()


def _loop_passes(x, carry, one_pass, params, cfg: TransformerConfig):
    """A looped model's passes: ``one_pass(first, x, carry) -> (x,
    carry)`` runs the ``n_layers`` body with layer l at cache layer
    ``first + l``, and the final norm closes each pass.  One
    ``lax.fori_loop``, so the body is traced once however many passes
    run, and ``carry`` (the donated pools) is updated in place."""
    assert cfg.family == "mha" and cfg.moe_router != "sigmoid", \
        "a looped model is dense MHA"

    def body(t, state):
        x, carry = state
        with jax.named_scope("loop_pass"):
            x, carry = one_pass(t * cfg.n_layers, x, carry)
            return _norm(x, params["ln_f"], cfg), carry

    return lax.fori_loop(0, cfg.loop_passes, body, (x, carry))


def _unembed(params, x, cfg: TransformerConfig):
    """Logits of hidden states ``x`` [..., T, E]: the ``unembed``
    matrix, or the tied embedding scaled by ``logit_scale``."""
    x = x.astype(cfg.jdtype)
    if cfg.tie_embeddings:
        return cfg.logit_scale * jnp.einsum(
            "bte,ve->btv", x, params["embed"], **_into_stream(cfg))
    return jnp.einsum("bte,ev->btv", x, params["unembed"],
                      **_into_stream(cfg))


def _dense_prefill(params, ids, cfg: TransformerConfig):
    """The prefill walk of an MHA model of one pass without a cache:
    ``(x [B, T, E] after the final norm, ks, vs)``, per layer the keys
    and values ``[B, T, H_kv, hd]`` as the cache holds them
    (post-rope)."""
    assert cfg.loop_passes == 1, "a looped model prefills into its pools"
    cache = _Cache(cfg)
    x = _prefill_walk(params, ids, cache, cfg)
    return x, *zip(*cache.kept["pages"])


def forward_prefill(params, ids, cfg: TransformerConfig):
    """Serving prefill: full forward over ``ids`` [B, T] returning
    ``(logits [B, T, V], k, v)`` with k/v ``[L, B, T, H, hd]`` — the
    post-rope per-layer keys/values the decode path needs cached.

    Single-chip math (ShardAxes()); right-padding is safe because
    attention is causal: positions < the true length never attend a pad
    token, so their K/V and logits are unaffected — the serving engine
    pads prompts to length buckets to bound jit recompilation.
    """
    x, ks, vs = _dense_prefill(params, ids, cfg)
    return _unembed(params, x, cfg), jnp.stack(ks), jnp.stack(vs)


def _logits_at(params, x, last_index, cfg: TransformerConfig):
    """Unembed ONE position per sequence: ``x [B, T, E]`` at
    ``last_index [B]`` -> ``[B, V]``.  The unembed is the model's
    largest single matmul at flagship vocab — projecting all T padded
    positions just to slice one row would multiply the serving
    prefill's dominant term by T."""
    x_last = jnp.take_along_axis(
        x, last_index[:, None, None].astype(jnp.int32), axis=1)  # [B,1,E]
    return _unembed(params, x_last, cfg)[:, 0]


def forward_prefill_last(params, ids, last_index, cfg: TransformerConfig):
    """Prefill with logits at ONE position per sequence:
    ``(logits [B, V], k, v)`` for ``last_index`` [B] (each sequence's
    final real token in a right-padded batch).  The K/V come back
    dense: what :func:`prefill_paged` puts into the pools."""
    x, ks, vs = _dense_prefill(params, ids, cfg)
    return (_logits_at(params, x, last_index, cfg), jnp.stack(ks),
            jnp.stack(vs))


def _paged(a, pool):
    """K or V of ONE sequence's whole blocks ``[..., 1, n x bs, H_kv,
    hd]`` as pages ``[..., n, bs, H_kv, hd]`` in the pool's dtype (a
    lane-flat page, :attr:`TransformerConfig.flat_pages`, has the heads
    side by side: ``[..., n, bs, H_kv x hd]``)."""
    bs = pool.shape[2]
    return a.reshape(a.shape[:-4] + (a.shape[-3] // bs, bs)
                     + pool.shape[3:]).astype(pool.dtype)


def _rope_window(x, positions, theta: float = 10000.0):
    """Rotary embedding for a decode WINDOW: x [B, S, H, D] with
    per-token positions [B, S] (speculative verify places each window
    token at its own absolute depth)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [B, S, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def forward_decode_paged(params, ids, positions, k_pool, v_pool,
                         block_tables, lengths, cfg: TransformerConfig):
    """:func:`decode_paged` of a model whose cache is one K and one V
    pool (the flagship, a looped model), the pools as arguments of
    their own: ``(logits [B, S, V], k_pool, v_pool)``.

    ids / positions [B, S] (S=1 plain decode, S=k+1 speculative
    verify); k_pool / v_pool [L, n_blocks, block_size, H_kv, hd] — the
    cache's device-resident pools; block_tables [B, W] int32 (rows
    padded with 0); lengths [B] int32 committed tokens per row.  Each
    layer writes the window's K/V into the pool at positions
    ``lengths[b] + s`` (physical address via the block table) and then
    attends positions ``<= lengths[b] + s`` through
    :func:`ops.paged_attention.paged_attention` in place: no dense
    gather, no re-placement copy.  The updated pools are the cache (the
    caller adopts them and advances each row's length by what it
    commits — window slots past that hold garbage)."""
    logits, pools, _ = decode_paged(params, ids, positions,
                                    (k_pool, v_pool), block_tables,
                                    lengths, (), cfg)
    return (logits,) + pools


# ---------------------------------------------------------------------------
# what the serving engine jits: a paged forward of any family with the
# greedy pick as its epilogue, so the logits never leave the device
# ---------------------------------------------------------------------------

def greedy_pick(logits):
    """The serving programs' epilogue, one for every family:
    ``(ids, finite)`` over ``logits [..., V]`` in the dtype they have.
    ``ids`` int32 is ``np.argmax``'s pick (the first index of the
    maximum; a row with a NaN lands on its first NaN); ``finite`` says
    whether the picked logit is finite, which it is not in a row with a
    NaN or a +inf, nor in one that is all -inf."""
    ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    picked = jnp.take_along_axis(logits, ids[..., None], axis=-1)[..., 0]
    return ids, jnp.isfinite(picked)


def _named(program, name):
    """``program`` known to a trace as ``jit(name)``."""
    if name:
        program.__name__ = program.__qualname__ = name
    return program


def picking_prefill(forward, name=None):
    """A prefill (:func:`prefill_paged`) as the engine runs it.  In
    place of ``last_index`` it takes ``(last_index [1], into [B, S], row
    [])``: the pick is also written into ``into[row, 0]`` (nowhere where
    ``row`` is out of range), so that a decode step can take it as a
    row's ``prev_ids`` before the host has read it.  Returns ``(ids
    [1], finite [1], into, *rest)`` where ``forward`` returns ``(logits
    [1, V], *rest)``.  The program is known to a trace by ``name``
    (default ``forward``'s own)."""
    @functools.wraps(forward)
    def program(params, ids, at, *args):
        last_index, into, row = at
        logits, *rest = forward(params, ids, last_index, *args)
        picked, finite = greedy_pick(logits)
        into = jnp.asarray(into).at[row, 0].set(picked[0], mode="drop")
        return (picked, finite, into, *rest)
    return _named(program, name)


def picking_decode(forward, name=None):
    """A decode step (:func:`decode_paged`) as the engine runs it.  In
    place of ``ids`` it takes ``(host_ids [B, S], prev_ids [B, S], src
    [B])``: row ``b`` consumes ``prev_ids[src[b]]``, the pick an earlier
    step or a prefill left on the device, or ``host_ids[b]`` where
    ``src[b] < 0`` (a row the host knows the last token of).  The row
    index lets a batch that compacted or grew between two steps run the
    one program.  Returns ``(ids [B, S], finite [B, S], *rest)`` where
    ``forward`` returns ``(logits [B, S, V], *rest)``; known to a trace
    by ``name`` (default ``forward``'s own)."""
    @functools.wraps(forward)
    def program(params, ids, *args):
        host_ids, prev_ids, src = ids
        fed = jnp.where(src[:, None] >= 0,
                        jnp.take(prev_ids, jnp.maximum(src, 0), axis=0),
                        host_ids)
        logits, *rest = forward(params, fed, *args)
        return (*greedy_pick(logits), *rest)
    return _named(program, name)


# ---------------------------------------------------------------------------
# the latent family's mixer and experts: multi-head latent attention
# whose paged cache holds one latent row per token and layer, a leading
# dense layer, and sigmoid-routed dropless experts of which this chip
# holds a share.
# ---------------------------------------------------------------------------

#: bytes one trip of the held experts' loop may hold in temporaries (the
#: gathered rows, their hidden activations, the product and its float32
#: addend: :func:`moe_tile`).  A compile for a described v5e of A.X-K1's
#: 16k prefill held 0.42 GB more temporaries at trips of 1,445 rows or
#: more, and none more at 968 (its row is 68 KiB)
MOE_TRIP_BYTES = 64 << 20

#: held pairs a layer expects per held expert below which its pairs are
#: sparse over its groups (:func:`moe_tile`)
MOE_SPARSE_PAIRS = 8


def moe_tile(n: int, cfg: TransformerConfig) -> int:
    """Rows of the sorted (token, pick) list one trip of
    :func:`_moe_held_ffn` takes, for a program of ``n`` tokens.

    XLA's grouped product (``ragged-dot``) pays for the groups its rows
    visit, and a call walks the whole stack.  Timed alone on a v5e
    (``scripts/time_ragged_dot.py``, Nemotron 3's stack of 640 groups):
    where a layer's pairs are dense over its held experts (a prefill),
    fewer and larger calls cost as much or more (a 1k prompt's 5,632
    pairs: 7.6 ms a layer in 512-row trips, 9.3 ms in one), so a trip
    takes 512 rows; where they are sparse, fewer than
    :data:`MOE_SPARSE_PAIRS` a held expert (a decode step), a 512-row
    trip spans most of the groups and the next walks them again (704
    pairs: 6.8 ms in two trips, 5.1 ms in one), so a trip holds twice the
    pairs the layer expects (``n`` x topk x the held share of the
    router; the routing's skew makes them more or less).  Never more
    than can land here, nor than :data:`MOE_TRIP_BYTES` over what a row
    of the trip holds.  What one trip does not hold takes another: the
    trip count follows the pairs, so the layer stays dropless whatever
    the routing."""
    k, x_l = cfg.moe_topk, cfg.n_experts
    expected = -(-n * k * x_l // (cfg.moe_n_routed or x_l))
    tile = 512
    if expected < MOE_SPARSE_PAIRS * x_l:
        tile = max(tile, 2 * expected)
    width = cfg.moe_latent or cfg.d_model
    mats = 2 if cfg.moe_act == "relu2" else 3
    item = jnp.dtype(cfg.jdtype).itemsize
    # the gathered row and its hidden activations in the weights' type,
    # the product in the stream's, its weighted addend in float32
    row = item * (width + mats * cfg.moe_d_ff) + width * (
        jnp.dtype(cfg.stream_dtype).itemsize + 4)
    tile = min(tile, n * min(k, x_l), MOE_TRIP_BYTES // row)
    return max(-(-tile // 8) * 8, 8)


def _yarn_inv_freq(cfg: TransformerConfig):
    """RoPE frequencies [qk_rope_head_dim / 2], yarn-blended: the
    published base frequencies where a dimension turns more than
    beta_fast times over the original context, those divided by the
    factor where it turns less than beta_slow times, a linear ramp
    between (numpy: these are trace-time constants)."""
    import numpy as np

    dim = cfg.qk_rope_head_dim
    base = cfg.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if cfg.rope_yarn_factor == 1.0:
        return jnp.asarray(base, jnp.float32)

    def turns_at(n_rot):  # the dimension that makes n_rot turns
        return dim * np.log(cfg.rope_yarn_original / (n_rot * 2 * np.pi)) \
            / (2 * np.log(cfg.rope_theta))

    low = max(np.floor(turns_at(cfg.rope_yarn_beta_fast)), 0)
    high = min(np.ceil(turns_at(cfg.rope_yarn_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    blended = base / cfg.rope_yarn_factor * ramp + base * (1 - ramp)
    return jnp.asarray(blended, jnp.float32)


def _mla_scale(cfg: TransformerConfig) -> float:
    """Softmax scale: qk width^-0.5 times yarn's mscale squared."""
    m = 1.0
    if cfg.rope_yarn_factor > 1.0:
        m += 0.1 * cfg.rope_yarn_mscale_all_dim * math.log(
            cfg.rope_yarn_factor)
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def _rope_rows(x, positions, inv_freq):
    """x [B, T, ..., D] rotated by halves at per-token positions
    [B, T] (mscale = mscale_all_dim: cos and sin are not scaled)."""
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [B,T,D/2]
    angles = angles.reshape(angles.shape[:2] + (1,) * (x.ndim - 3)
                            + angles.shape[-1:])
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def _mla_project(xn, p, positions, cfg: TransformerConfig):
    """The MLA projections of normed activations ``xn`` [B, T, E] at
    ``positions`` [B, T]: ``(q_nope [B,T,H,nope], q_pe [B,T,H,pe],
    row [B,T,rkv+pe])`` where ``row`` = [rms(c_kv) | rope(k_pe)] is
    what the cache holds per token and layer."""
    rkv, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    inv_freq = _yarn_inv_freq(cfg)
    if cfg.q_lora_rank:
        c_q = rms_norm(jnp.einsum("bte,er->btr", xn, p["w_qa"]), p["q_norm"])
        q = jnp.einsum("btr,rhd->bthd", c_q, p["w_qb"])
    else:
        q = jnp.einsum("bte,ehd->bthd", xn, p["w_q"])
    kva = jnp.einsum("bte,er->btr", xn, p["w_kva"])
    c_kv = rms_norm(kva[..., :rkv], p["kv_norm"])
    k_pe = _rope_rows(kva[..., rkv:], positions, inv_freq)
    q_pe = _rope_rows(q[..., nope:], positions, inv_freq)
    return q[..., :nope], q_pe, jnp.concatenate([c_kv, k_pe], -1)


def _mla_prefill(xn, layer, cache, cfg: TransformerConfig):
    """Causal MLA over a whole sequence, up-projected: keys
    [k_nope | k_pe] at qk width and values at v width per head, through
    the flash kernel (or its lax twin).  Returns ``(y [B,T,E], row)``,
    ``row`` what the cache keeps of each token."""
    rkv, nope, p = cfg.kv_lora_rank, cfg.qk_nope_head_dim, layer.p
    q_nope, q_pe, row = _mla_project(xn, p, cache.positions, cfg)
    kv = jnp.einsum("btr,rhd->bthd", row[..., :rkv], p["w_kvb"])
    k_pe = jnp.broadcast_to(row[:, :, None, rkv:],
                            q_pe.shape[:2] + (cfg.n_heads, q_pe.shape[-1]))
    q = jnp.concatenate([q_nope, q_pe], -1)
    k = jnp.concatenate([kv[..., :nope], k_pe], -1)
    # the scope states the call's T: the benchmark's roofline reader
    # counts each call's operations at its own length
    with jax.named_scope(f"prefill_attn_t{q.shape[1]}"):
        o = _causal_attention(q, k, kv[..., nope:], scale=_mla_scale(cfg))
    return jnp.einsum("bthd,hde->bte", o, p["wo"]), row


def _mla_absorbed_queries(q_nope, q_pe, p, cfg: TransformerConfig):
    """Decode's queries against the latent rows themselves: the key
    up-projection absorbed into q (q' = q_nope W_kvb,k^T), beside q_pe."""
    w_k = p["w_kvb"][..., :cfg.qk_nope_head_dim]                 # [rkv,H,nope]
    q_abs = jnp.einsum("bshd,rhd->bshr", q_nope, w_k)
    return jnp.concatenate([q_abs.astype(q_pe.dtype), q_pe], -1)


def _mla_absorbed_output(o_lat, p, cfg: TransformerConfig):
    """o' = p . c_kv per head [B,S,H,rkv] -> the value up-projection
    and the output projection."""
    w_v = p["w_kvb"][..., cfg.qk_nope_head_dim:]                 # [rkv,H,v]
    o = jnp.einsum("bshr,rhd->bshd", o_lat, w_v)
    return jnp.einsum("bshd,hde->bse", o, p["wo"])


def _group_limited_top_k(scores, bias, cfg: TransformerConfig):
    """Group-limited selection with a correction bias: ``scores`` [n,
    moe_n_routed] float32 in ``moe_n_group`` groups of neighbours (a
    group is what one device of the deployment holds); selection runs on
    ``scores + bias``: a group's score is the sum of its two largest,
    the ``moe_topk_group`` best groups stay and the ``moe_topk`` largest
    inside them are the picks.  Returns ``(the picks' UNBIASED scores
    [n, k], their experts [n, k])``: the bias steers load, not weight."""
    n, routed = scores.shape
    biased = scores if bias is None else scores + bias.astype(jnp.float32)
    groups = biased.reshape(n, cfg.moe_n_group, routed // cfg.moe_n_group)
    group_score = jnp.sum(lax.top_k(groups, 2)[0], axis=-1)
    kept = lax.top_k(group_score, cfg.moe_topk_group)[1]         # [n, kg]
    keep = jnp.any(kept[:, :, None] == jnp.arange(cfg.moe_n_group), axis=1)
    inside = jnp.where(keep[:, :, None], groups, -jnp.inf).reshape(n, routed)
    top_i = lax.top_k(inside, cfg.moe_topk)[1]
    return jnp.take_along_axis(scores, top_i, axis=-1), top_i


def _moe_held_ffn(x, p, cfg: TransformerConfig, valid=None,
                  first_group: int = 0):
    """Sigmoid-routed experts of which this chip holds a share, with
    the shared expert: dropless.

    x [B, T, E].  The router scores all ``moe_n_routed`` experts in
    float32, picks the ``moe_topk`` largest (or, with ``moe_n_group``,
    :func:`_group_limited_top_k`'s) and weighs them
    ``moe_routed_scale * s_i / (sum of the picked s + 1e-20)`` (the
    normaliser is over all picks, held or not).  The (token, pick)
    pairs whose expert is held here, [moe_held_start, + n_experts), are
    sorted by expert; the sorted list is walked in trips of
    :func:`moe_tile` rows under a trip count that follows how many
    pairs landed here, each trip one grouped product per matrix
    (``lax.ragged_dot``), scattered back onto its tokens.  No capacity,
    no bound that can overflow: every held pair is computed whatever
    the routing, and what the absent experts would add is left out.
    On one chip the layer runs without its exchange.

    ``p["w_in"]`` / ``["w_gate"]`` / ``["w_out"]`` are [G, E, F] /
    [G, F, E] with this layer's experts at groups [first_group,
    first_group + n_experts): the callers pass every expert layer's
    stack as ONE group axis, because a grouped product takes its
    operand whole and a per-layer slice of the stack would be copied
    (336 MB a matrix at A.X-K1's widths); the other layers' groups are
    empty and cost nothing.

    With ``moe_latent`` the experts work in a latent (``w_in`` [G, latent,
    F], ``w_out`` [G, F, latent]): the token goes down ``p["w_down"]``
    ONCE, the gather, the grouped products, the weighing and the
    scatter-add run at the latent width, and the sum of the held picks
    goes up ``p["w_up"]`` ONCE (the map is linear, so the shares of a
    deployment's chips add up, exchanged at the latent width); the
    router still reads ``x``.  ``moe_act`` "relu2": an expert is ``W_out
    relu(W_in u)^2``, no gate matrix, and so is the shared expert.  A
    correction bias ``p["gate_bias"]`` without groups steers the plain
    top-k: the picks are the largest ``s + b``, their weights come from
    ``s``.

    Returns ``(y [B, T, E], counts [n_experts + 2] int32)``: pairs per
    held expert, then all pairs routed anywhere, both over the tokens
    ``valid`` [B, T] marks (default all), then the grouped products
    invoked (trips x matrices: the trips walk every held pair, pad
    tokens' too)."""
    b, t, e = x.shape
    n, k, x_l = b * t, cfg.moe_topk, cfg.n_experts
    # float32 from the operands as stored: products of bf16 values are
    # exact in float32, so accumulating there IS the float32 router,
    # without a float32 copy of the activations (under a float32
    # residual stream the router reads x unrounded; the experts' products
    # take it in the weights' type)
    scores = jax.nn.sigmoid(jnp.einsum(
        "ne,ex->nx", x.reshape(n, e), p["gate"],
        preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST))
    xf = x.reshape(n, e).astype(cfg.jdtype)
    if cfg.moe_n_group:
        top_s, top_i = _group_limited_top_k(scores, p.get("gate_bias"), cfg)
    elif p.get("gate_bias") is not None:
        top_i = lax.top_k(scores + p["gate_bias"].astype(jnp.float32), k)[1]
        top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    else:
        top_s, top_i = lax.top_k(scores, k)                      # [n, k]
    relu2 = cfg.moe_act == "relu2"
    xe = xf  # what the experts read: the token, or its latent
    if cfg.moe_latent:
        with jax.named_scope("latent_down"):
            xe = jnp.einsum("ne,el->nl", xf, p["w_down"])
    weight = cfg.moe_routed_scale * top_s / (
        jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)

    # the pairs that landed here, sorted by held expert; the others go
    # to the end of the list under the key x_l
    local = (top_i >= cfg.moe_held_start) & (
        top_i < cfg.moe_held_start + x_l)
    key = jnp.where(local, top_i - cfg.moe_held_start, x_l)      # [n, k]

    def per_expert(keys):
        return jnp.zeros(x_l + 1, jnp.int32).at[keys.reshape(-1)].add(
            1)[:x_l]

    order = jnp.argsort(key.reshape(-1), stable=True)
    sizes = per_expert(key)
    ends = jnp.cumsum(sizes)
    n_held = ends[-1]
    tile = moe_tile(n, cfg)
    trips = -(-n_held // tile)
    pad = -(n * k) % tile
    token = jnp.pad(order // k, (0, pad))                        # [pairs]
    w_sorted = jnp.pad(weight.reshape(-1)[order], (0, pad))
    n_groups = p["w_in"].shape[0]

    def one_tile(i, y):
        lo = i * tile
        rows = lax.dynamic_slice_in_dim(token, lo, tile)
        # this tile's rows of each expert: the sorted groups cut at its edges
        hi = jnp.clip(ends, lo, lo + tile)
        groups = jnp.zeros(n_groups, jnp.int32).at[
            first_group:first_group + x_l].set(
                hi - jnp.concatenate([jnp.full((1,), lo, hi.dtype), hi[:-1]]))
        xs = jnp.take(xe, rows, axis=0)
        hidden = lax.ragged_dot(xs, p["w_in"], groups)
        hidden = jnp.square(jax.nn.relu(hidden)) if relu2 \
            else hidden * jax.nn.silu(lax.ragged_dot(xs, p["w_gate"], groups))
        # under a float32 stream the pairs' results are weighed and
        # summed as the product accumulated them
        out = lax.ragged_dot(hidden, p["w_out"], groups,
                             preferred_element_type=None
                             if x.dtype == xf.dtype else jnp.float32)
        # rows past the held pairs belong to no group: their product is
        # whatever the buffer held, so they are selected out, not scaled
        held = (lo + jnp.arange(tile) < n_held)[:, None]
        w = lax.dynamic_slice_in_dim(w_sorted, lo, tile)[:, None]
        return y.at[rows].add(jnp.where(held, out.astype(jnp.float32) * w, 0.0))

    y = lax.fori_loop(0, trips, one_tile,
                      jnp.zeros((n, xe.shape[-1]), jnp.float32))
    y = y.astype(x.dtype)
    if cfg.moe_latent:
        with jax.named_scope("latent_up"):
            y = jnp.einsum("nl,le->ne", y.astype(cfg.jdtype), p["w_up"],
                           **_into_stream(cfg))
    y = y.reshape(b, t, e)
    if cfg.moe_n_shared and relu2:
        hidden = jnp.square(jax.nn.relu(jnp.einsum(
            "bte,ef->btf", xf.reshape(b, t, e), p["s_in"])))
        y = y + jnp.einsum("btf,fe->bte", hidden, p["s_out"],
                           **_into_stream(cfg))
    elif cfg.moe_n_shared:
        # the shared experts lie side by side in one SwiGLU, which sums
        # them; their average is that over their number
        shared = swiglu_ffn(xf.reshape(b, t, e), p["s_in"], p["s_gate"],
                            p["s_out"], ShardAxes(),
                            **({} if x.dtype == xf.dtype
                               else {"out_dtype": x.dtype}))
        y = y + (shared / cfg.moe_n_shared if cfg.moe_shared_average
                 else shared)
    routed = jnp.asarray(n * k, jnp.int32)
    if valid is not None:
        sizes = per_expert(jnp.where(valid.reshape(n, 1), key, x_l))
        routed = jnp.sum(valid.astype(jnp.int32)) * k
    calls = trips * (2 if relu2 else 3)
    return y, jnp.concatenate([sizes, routed.reshape(1), calls.reshape(1)])


_EXPERT_STACKS = ("w_in", "w_gate", "w_out")


def _write_prompt_rows(pool, layer: int, block_ids, row):
    """A whole prompt's latent rows ``row`` [1, T, r] into pages
    ``block_ids`` of one layer, each page ``[r, slot]``."""
    bs = pool.shape[3]
    return pool.at[layer, block_ids].set(jnp.swapaxes(
        row.reshape(-1, bs, row.shape[-1]), 1, 2).astype(pool.dtype))


def _write_latent_rows(pool, layer: int, blocks, slots, row):
    """The decode window's latent rows ``row`` [B, S, r] into pages
    ``blocks`` [B, S] at ``slots`` [B, S] of one layer (a block index
    past the pool drops the write: dead rows).  Whole pages are read,
    given their new column and written back, one window position at a
    time: a page is [r, slot], and a scatter of columns would have the
    chip's compiler lay the whole pool row-minor and copy it for the
    kernel in every layer, where a page is 147 KB."""
    bs = pool.shape[3]
    for s in range(row.shape[1]):
        pages = pool.at[layer, blocks[:, s]].get(mode="clip")      # [B, r, bs]
        mine = (jnp.arange(bs)[None, None, :] == slots[:, s, None, None])
        pages = jnp.where(mine, row[:, s, :, None].astype(pool.dtype), pages)
        pool = pool.at[layer, blocks[:, s]].set(pages, mode="drop")
    return pool


def _mla_decode(xn, layer, li: int, cache, cfg: TransformerConfig):
    """One layer's absorbed latent attention of a decode window: the
    window's rows into pool layer ``li`` at ``lengths[b] + s``, then the
    rows themselves attended (K = the row, V = its first kv_lora_rank
    values) through :func:`ops.paged_attention.latent_paged_attention`,
    for all heads from the one page.  Returns attention's addend [B, S,
    E]."""
    from ..ops import paged_attention as _paged

    p = layer.p
    pool, = cache.get("pages")
    wb, ws = cache.where["pages"]
    q_nope, q_pe, row = _mla_project(xn, p, cache.positions, cfg)
    pool = _write_latent_rows(pool, li, wb, ws, row)
    cache.set("pages", pool)
    # the layers' pools as one run of pages: a per-layer slice of the
    # pool would be copied for the kernel
    o_lat = _paged.latent_paged_attention(
        _mla_absorbed_queries(q_nope, q_pe, p, cfg),
        pool.reshape((-1,) + pool.shape[2:]),
        cache.tables["pages"] + li * pool.shape[1], cache.lengths,
        v_dim=cfg.kv_lora_rank, scale=_mla_scale(cfg))
    return _mla_absorbed_output(o_lat, p, cfg)


# ---------------------------------------------------------------------------
# the hybrid family's KDA mixer (ops/kda.py), in most of its layers, its
# per-sequence state in a slot of the cache manager; MLA in the rest
# with its rows in the paged pool; the latent family's FFNs, the router
# group-limited.  One token a decode step.
# ---------------------------------------------------------------------------


def _kda_project(xn, p, cfg: TransformerConfig):
    """The KDA projections of normed activations ``xn`` [B, T, E]:
    ``(qkv [B, T, 3 H d]`` as projected, before the convolution, ``g
    [B, T, H, d]`` the log decay in (kda_lower_bound, 0), ``beta
    [B, T, H]``, ``gate [B, T, H])``, the last three in float32."""
    f32 = {"preferred_element_type": jnp.float32}
    qkv = jnp.einsum("bte,ef->btf", xn, p["w_qkv"])
    a = jnp.einsum("bte,ef->btf", xn, p["w_a"], **f32).reshape(
        xn.shape[:2] + p["dt_bias"].shape)
    g = cfg.kda_lower_bound * jax.nn.sigmoid(
        jnp.exp(p["a_log"])[:, None] * (a + p["dt_bias"]))
    beta = jax.nn.sigmoid(jnp.einsum("bte,eh->bth", xn, p["w_beta"], **f32))
    gate = jax.nn.sigmoid(jnp.einsum("bte,eh->bth", xn, p["w_og"], **f32))
    return qkv, g, beta, gate


def _kda_qkv(conv_out, cfg: TransformerConfig):
    """The convolution's output [..., 3 H d] (float32) through SiLU,
    split by head, q and k L2-normalised and q scaled by d^-1/2."""
    h, d = cfg.n_heads, cfg.head_dim
    y = jax.nn.silu(conv_out).reshape(conv_out.shape[:-1] + (3, h, d))
    q, k, v = y[..., 0, :, :], y[..., 1, :, :], y[..., 2, :, :]

    def unit(a):
        return a * lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

    return unit(q) * d ** -0.5, unit(k), v


def _kda_output(o, gate, p, cfg: TransformerConfig):
    """o [B, T, H, d] float32: RMSNorm per head, the head-wise gate,
    the output projection."""
    o = rms_norm(o, p["o_norm"].astype(jnp.float32)) * gate[..., None]
    return jnp.einsum("bthd,hde->bte", o.astype(cfg.jdtype), p["wo"])


def _kda_prefill(xn, layer, cache, cfg: TransformerConfig):
    """KDA over ONE whole sequence ``xn`` [1, T, E] from a zero state.
    ``cache.valid`` [1, T] marks the prompt's real tokens: a padded
    position neither decays nor writes the state (beta = 0, g = 0), and
    the convolution's tail is taken at ``cache.last_index`` [1].
    Chunk-wise (``ops.kda.kda_chunk_scan``).  Returns ``(attention's
    addend [1, T, E], (S^T [H, d, d], tail [W - 1, 3 H d]))``."""
    from ..ops import kda as _kda

    p, valid, last_index = layer.p, cache.valid, cache.last_index
    w = cfg.kda_conv_size
    qkv, g, beta, gate = _kda_project(xn, p, cfg)
    t = qkv.shape[1]
    with jax.named_scope("conv"):
        padded = jnp.pad(qkv, ((0, 0), (w - 1, 0), (0, 0)))
        conv = p["conv"].astype(jnp.float32)
        y = sum(conv[j] * padded[:, j:j + t] for j in range(w))
        # the tail is projected again from its own w - 1 tokens: cut
        # out of ``qkv``, the slice was scheduled with the slot's write
        # at the program's end and kept every KDA layer's projection
        # alive until then (2.1 GB at 8k tokens)
        at = last_index[0] - (w - 2) + jnp.arange(w - 1)
        tail = jnp.where(
            (at >= 0)[:, None],
            jnp.einsum("te,ef->tf", jnp.take(xn[0], jnp.maximum(at, 0), 0),
                       p["w_qkv"]), 0)
    q, k, v = _kda_qkv(y, cfg)
    with jax.named_scope("chunk_scan"):
        o, s_t = _kda.kda_chunk_scan(
            q[0], k[0], v[0], jnp.where(valid[0, :, None, None], g[0], 0.0),
            jnp.where(valid[0, :, None], beta[0], 0.0))
    return _kda_output(o[None], gate, p, cfg), (s_t, tail)


def _kda_decode(xn, layer, li: int, cache, cfg: TransformerConfig):
    """One KDA layer of one decode token a row: the convolution over
    the slot's tail and the new input, then ``ops.kda.kda_state_step``
    on the row's state in place.  A dead row reads slot 0's tail, which
    is harmless, and writes nothing.  Returns attention's addend [B, 1,
    E]."""
    from ..ops import kda as _kda

    p, slots, live = layer.p, cache.slots, cache.live
    state, tails = cache.get("state")
    n_slots = state.shape[1]
    qkv, g, beta, gate = _kda_project(xn, p, cfg)
    with jax.named_scope("conv"):
        window = jnp.concatenate(
            [tails[li, slots].reshape(qkv.shape[0], -1, qkv.shape[-1]), qkv],
            axis=1)
        y = jnp.sum(window * p["conv"].astype(jnp.float32), axis=1)
        tails = tails.at[li, jnp.where(live, slots, n_slots)].set(
            window[:, 1:].reshape((-1,) + tails.shape[2:]), mode="drop")
    q, k, v = _kda_qkv(y, cfg)
    with jax.named_scope("state_step"):
        # every layer's slots as one run: a per-layer slice of the
        # state would be copied for the kernel, 134 MB a layer
        o, flat = _kda.kda_state_step(
            q, k, v, g[:, 0], beta[:, 0],
            state.reshape((-1,) + state.shape[2:]), slots + li * n_slots,
            live)
    y = _kda_output(o[:, None], gate, p, cfg)
    cache.set("state", flat.reshape(state.shape), tails)
    return y


# ---------------------------------------------------------------------------
# the Mamba-2 mixer of a model under a layer pattern (Nemotron-H's
# block: every layer ONE thing under one norm).  "M" a Mamba-2 mixer
# (ops/mamba2.py), its per-sequence state and convolution tail in a
# slot of the cache manager; "*" grouped-query attention without
# positions, its K/V in the paged pool; "E" the held experts in their
# latent.  One token a decode step.
# ---------------------------------------------------------------------------


def _mamba_split(zxbcdt, p, cfg: TransformerConfig):
    """The input projection's columns ``[..., d_inner + conv_dim + H]``:
    ``(z, xBC as projected, dt = softplus(dt + dt_bias) in float32)``."""
    inner, conv = cfg.mamba_d_inner, cfg.mamba_conv_dim
    dt = jax.nn.softplus(
        zxbcdt[..., inner + conv:].astype(jnp.float32) + p["dt_bias"])
    return zxbcdt[..., :inner], zxbcdt[..., inner:inner + conv], dt


def _mamba_xbc(conv_out, cfg: TransformerConfig):
    """The convolution's output [..., conv_dim] (float32, bias added)
    through SiLU and split: ``(x [..., H, P], B [..., G, N], C [..., G,
    N])``."""
    inner, g, n = cfg.mamba_d_inner, cfg.mamba_n_groups, cfg.mamba_state
    y = jax.nn.silu(conv_out)
    lead = y.shape[:-1]
    return (y[..., :inner].reshape(lead + (cfg.mamba_n_heads,
                                           cfg.mamba_head_dim)),
            y[..., inner:inner + g * n].reshape(lead + (g, n)),
            y[..., inner + g * n:].reshape(lead + (g, n)))


def _mamba_output(y, z, p, cfg: TransformerConfig):
    """y [B, T, H, P] float32 (skip term included) and the gate ``z``
    [B, T, d_inner]: the gate BEFORE the norm, RMSNorm over each
    group's channels, the norm's weight, the output projection."""
    with jax.named_scope("gate_norm"):
        y = y.reshape(z.shape) * jax.nn.silu(z.astype(jnp.float32))
        grouped = y.reshape(y.shape[:-1] + (cfg.mamba_n_groups, -1))
        var = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
        y = (grouped * lax.rsqrt(var + cfg.norm_eps)).reshape(y.shape) \
            * p["norm"].astype(jnp.float32)
    with jax.named_scope("out_proj"):
        return jnp.einsum("bti,ie->bte", y.astype(cfg.jdtype), p["out_proj"],
                          **_into_stream(cfg))


#: rows of a prompt one pass of a Mamba-2 prefill layer takes where the
#: prompt is longer: the projection, the convolution's float32 sum, x,
#: B, C and the scan's output of 8k rows are 2.2 GB beside 12.3 GB of
#: weights, state and pool; the state and the convolution's last inputs
#: are carried from one pass to the next
MAMBA_PREFILL_ROWS = 2048


def _mamba_rows(xa, valid, carry, p, cfg: TransformerConfig):
    """Rows ``xa`` [1, R, E] (normed, the products' operand) of ONE
    sequence through a Mamba-2 mixer, after ``carry``: the state ``[H,
    P, N]`` and the convolution's inputs ``[W - 1, conv_dim]`` that the
    rows before them left.  Returns ``(the mixer's addend [1, R, E],
    carry)``."""
    from ..ops import mamba2 as _mamba2

    state, before = carry
    r = xa.shape[1]
    with jax.named_scope("in_proj"):
        z, xbc, dt = _mamba_split(
            jnp.einsum("bte,ef->btf", xa, p["in_proj"]), p, cfg)
    with jax.named_scope("conv"):
        window = jnp.concatenate([before[None].astype(xbc.dtype), xbc], 1)
        conv = p["conv"].astype(jnp.float32)
        y = sum(conv[j] * window[:, j:j + r]
                for j in range(cfg.mamba_conv_size)) \
            + p["conv_b"].astype(jnp.float32)
    x, b, c = _mamba_xbc(y, cfg)
    with jax.named_scope("chunk_scan"):
        o, state = _mamba2.ssd_chunk_scan(
            x[0], jnp.where(valid[0, :, None], dt[0], 0.0),
            jnp.exp(p["a_log"]), b[0], c[0], chunk=cfg.mamba_chunk,
            state=state)
        o = o[None] + p["d"][:, None] * x
    return _mamba_output(o, z, p, cfg), (state, window[0, r:])


def _mamba_prefill(xn, layer, cache, cfg: TransformerConfig):
    """A Mamba-2 mixer over ONE whole sequence ``xn`` [1, T, E] from a
    zero state, :data:`MAMBA_PREFILL_ROWS` rows a pass where the prompt
    is longer (a multiple of it), chunk-wise
    (``ops.mamba2.ssd_chunk_scan``).  ``cache.valid`` [1, T] marks the
    prompt's real tokens: a padded position neither decays nor writes
    the state (dt = 0), and the convolution's tail is taken at
    ``cache.last_index`` [1].  Returns ``(the mixer's addend [1, T, E],
    (state [H, P, N], tail [W - 1, conv_dim]))``."""
    p, valid, last_index = layer.p, cache.valid, cache.last_index
    w = cfg.mamba_conv_size
    _, t, e = xn.shape
    xa = xn.astype(cfg.jdtype)
    carry = (jnp.zeros((cfg.mamba_n_heads, cfg.mamba_head_dim,
                        cfg.mamba_state), jnp.float32),
             jnp.zeros((w - 1, cfg.mamba_conv_dim), cfg.jdtype))
    rows = MAMBA_PREFILL_ROWS
    if t > rows and t % rows == 0:
        def one(carry, xs):
            y, carry = _mamba_rows(xs[0][None], xs[1][None], carry, p, cfg)
            return carry, y[0]

        (state, _), y = lax.scan(one, carry, (
            xa[0].reshape(t // rows, rows, e),
            valid[0].reshape(t // rows, rows)))
        y = y.reshape(1, t, e)
    else:
        y, (state, _) = _mamba_rows(xa, valid, carry, p, cfg)
    with jax.named_scope("conv"):
        # the tail is projected again from its own w - 1 tokens, as
        # KDA's is: cut out of the projection, the slice would keep
        # every layer's alive until the slot's write
        at = last_index[0] - (w - 2) + jnp.arange(w - 1)
        tail = jnp.where(
            (at >= 0)[:, None],
            _mamba_split(jnp.einsum(
                "te,ef->tf", jnp.take(xa[0], jnp.maximum(at, 0), 0),
                p["in_proj"]), p, cfg)[1], 0)
    return y, (state, tail)


def _mamba_decode(xn, layer, li: int, cache, cfg: TransformerConfig):
    """One Mamba-2 layer of one decode token a row: the convolution
    over the slot's tail and the new input, then
    ``ops.mamba2.ssm_state_step`` on the row's state in place.  A dead
    row reads slot 0's tail, which is harmless, and writes nothing.
    Returns the mixer's addend [B, 1, E]."""
    from ..ops import mamba2 as _mamba2

    p, slots, live = layer.p, cache.slots, cache.live
    state, tails = cache.get("state")
    n_slots = state.shape[1]
    with jax.named_scope("in_proj"):
        z, xbc, dt = _mamba_split(jnp.einsum(
            "bte,ef->btf", xn.astype(cfg.jdtype), p["in_proj"]), p, cfg)
    with jax.named_scope("conv"):
        window = jnp.concatenate(
            [tails[li, slots].reshape(xbc.shape[0], -1, xbc.shape[-1]), xbc],
            axis=1)
        y = jnp.sum(window * p["conv"].astype(jnp.float32), axis=1) \
            + p["conv_b"].astype(jnp.float32)
        tails = tails.at[li, jnp.where(live, slots, n_slots)].set(
            window[:, 1:].reshape((-1,) + tails.shape[2:]), mode="drop")
    x, b, c = _mamba_xbc(y, cfg)                       # [B, H, P], [B, G, N]
    dt = dt[:, 0]                                      # [B, H]
    with jax.named_scope("state_step"):
        # every layer's slots as one run: a per-layer slice of the
        # state would be copied for the kernel, 537 MB a layer
        o, flat = _mamba2.ssm_state_step(
            x * dt[..., None], jnp.exp(-dt * jnp.exp(p["a_log"])), b, c,
            state.reshape((-1,) + state.shape[2:]), slots + li * n_slots,
            live)
        o = o + p["d"][:, None] * x
    y = _mamba_output(o[:, None], z, p, cfg)
    cache.set("state", flat.reshape(state.shape), tails)
    return y


# ---------------------------------------------------------------------------
# the serving walk: ONE prefill and ONE decode program for every family.
# A layer is a mixer (attention of a kind, latent attention, KDA, Mamba-2,
# or under a layer pattern the held experts alone) and, outside a layer
# pattern, an FFN half.  Each kind's mixer has a prefill and a decode
# half (_MIXERS), which read and write that kind's arrays of the cache
# through one handle (_Cache).  Paged path only: the device arrays are
# the cache.
# ---------------------------------------------------------------------------


class _Layer(NamedTuple):
    """One layer as the walk takes it: its kind (``cfg.layer_kinds``),
    its index among the layers of that kind (its index into the kind's
    pool layers or state slots), its params, and where its held experts
    start in the one stack of them (None where it holds none:
    :func:`_moe_held_ffn`)."""
    kind: str
    i: int
    p: dict
    first_group: Optional[int]


def _one_sublayer(cfg: TransformerConfig) -> bool:
    """Whether a layer is ONE sublayer under one norm (a layer
    pattern's), not a mixer followed by an FFN half."""
    return bool(cfg.layer_pattern)


def _stacked_halves(params):
    """The layers of a stacked tree (the latent and hybrid families')
    as ``(layer params, first_group)`` in order: the leading dense group
    (``first_group`` None), then the expert layers, whose routed experts
    stay ONE stack [S * L/S * X, ...] with the layer's own at
    ``first_group``.  A latent tree's attention rides in the same
    dicts."""
    dense, blocks = params["dense"], params["blocks"]
    for i in range(dense["ln2"].shape[0]):
        yield jax.tree.map(lambda a: a[i], dense), None
    n_stages, lps = blocks["ln2"].shape[:2]
    stacks = {name: blocks[name].reshape((-1,) + blocks[name].shape[3:])
              for name in _EXPERT_STACKS}
    n_held = blocks["w_in"].shape[2]
    rest = {name: a for name, a in blocks.items() if name not in stacks}
    for s in range(n_stages):
        for i in range(lps):
            yield ({**_layer_params(rest, s, i), **stacks},
                   (s * lps + i) * n_held)


def _listed_halves(params, cfg: TransformerConfig):
    """The layers of a tree that lists them (``layers``, one dict a
    layer) as ``(layer params, first_group)``: where the tree has held
    experts (``experts``, one stack), every layer that has a router
    (under a layer pattern an expert layer; not a leading dense one)
    carries the stack, its own at ``first_group``."""
    experts = params.get("experts")
    held = 0
    for p in params["layers"]:
        if experts is None or "gate" not in p:
            yield p, None
        else:
            yield {**p, **experts}, held * cfg.n_experts
            held += 1


def _serving_layers(params, cfg: TransformerConfig):
    """Yields every layer of any family's tree, in order, as a
    :class:`_Layer`.  A stacked MHA tree's per-layer slices are taken
    here, inside the caller's program (:func:`per_layer_params`); a
    hybrid's attention is stacked by kind (``params[kind]``) beside its
    FFN halves."""
    if "dense" in params:
        halves = _stacked_halves(params)
    else:
        halves = _listed_halves(per_layer_params(params), cfg)
    seen = {}
    for kind, (p, first_group) in zip(cfg.layer_kinds, halves):
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        if kind in params:
            p = {**p, **jax.tree.map(lambda a: a[i], params[kind])}
        yield _Layer(kind, i, p, first_group)


def _ffn(xn, layer: _Layer, valid, counts, cfg: TransformerConfig):
    """A layer's FFN on normed activations: the held experts (their
    routing counts over ``valid`` appended to ``counts``), one dense
    SwiGLU (a looped model's, a stacked tree's leading dense layers), or
    the trained mixtures."""
    p = layer.p
    if layer.first_group is not None:
        with jax.named_scope("moe"):
            y, c = _moe_held_ffn(xn, p, cfg, valid, layer.first_group)
        counts.append(c)
        return y
    if p["w_in"].ndim == 2:
        return swiglu_ffn(xn.astype(cfg.jdtype), p["w_in"], p["w_gate"],
                          p["w_out"], ShardAxes(),
                          out_dtype=_into_stream(cfg).get(
                              "preferred_element_type"))
    return _moe_ffn(xn.astype(cfg.jdtype), p, ShardAxes(), cfg)


def _ffn_half(x, xn, y, layer: _Layer, valid, counts,
              cfg: TransformerConfig):
    """The residual stream after a layer.  Sequential block: ``x``
    already holds the mixer's addend (``y`` None), and the FFN takes a
    second norm of it.  Parallel block: the FFN takes the layer's one
    normed input ``xn`` and both addends land together.  A stacked
    tree's dense FFN and its addend are named "mlp"."""
    if cfg.parallel_block:
        return x + y + _ffn(xn, layer, valid, counts, cfg)
    xn = _norm(x, layer.p["ln2"], cfg)
    with _scope("mlp" if cfg.latent and layer.first_group is None
                else None):
        return x + _post_norm(_ffn(xn, layer, valid, counts, cfg), layer.p,
                              "ln2_post", cfg)


def _mixer_addend(x, y, p, cfg: TransformerConfig):
    """``(x, y)`` after the mixer's addend ``y`` joined the stream, which
    in a parallel block waits for the FFN's (``y`` stays)."""
    y = _post_norm(y, p, "ln1_post", cfg)
    if cfg.parallel_block:
        return x, y
    return x + y, None


def _attention_keys(xn, layer: _Layer, cfg: TransformerConfig):
    """An attention layer's keys and values of a whole prompt (normed
    ``xn`` [B, T, E]), ``[B, T, H_kv, hd]`` as the cache holds them:
    rotated where the kind rotates, none otherwise."""
    xa = xn.astype(cfg.jdtype)  # the products' operand
    k = _heads(xa, layer.p["wk"], cfg)
    v = _heads(xa, layer.p["wv"], cfg)
    if _rotates(layer.kind, cfg):
        k = _rotate(rope, k, jnp.arange(xn.shape[1]), layer.kind, cfg)
    return k, v


def _attention_rows(xn, layer: _Layer, cfg: TransformerConfig, kv, first,
                    n_chunks: int):
    """Rows ``[first, first + R)`` of a prompt (normed ``xn`` [B, R, E])
    attending its keys and values ``kv`` causally, a sliding layer
    within its window: the addend [B, R, E]."""
    k, v = kv
    t = k.shape[1]
    span = cfg.sliding_window if layer.kind == "sliding" else 0
    q = _heads(xn.astype(cfg.jdtype), layer.p["wq"], cfg)
    if _rotates(layer.kind, cfg):
        q = _rotate(rope, q, first + jnp.arange(xn.shape[1]), layer.kind, cfg)
    # the scope states the call's T, window and chunks: the benchmark's
    # roofline reader counts each call's operations from them
    with _scope(_MIXERS[layer.kind].scopes[0]
                and f"prefill_attn_t{t}_w{span}_c{n_chunks}"):
        o = _causal_attention(q, k, v, span=span,
                              q_offset=None if n_chunks == 1 else first,
                              sink=layer.p.get("sink"))
    return _attention_out(o, layer.p, cfg)


def _attention_decode(xn, layer: _Layer, li, cache, cfg: TransformerConfig):
    """An attention layer of a decode window: the window's K/V written
    into its pools' layer ``li`` at ``lengths[b] + s`` (dead rows drop
    their write), then attended in place through
    :func:`ops.paged_attention.paged_attention` (``paged_attn``, one
    call a layer), which reads through the table only the pages a live
    row's positions fill: a padded table entry or a dead row costs it
    nothing.  A sliding layer writes its ring and attends its window
    alone.  Returns the addend [B, S, E]."""
    from ..ops import paged_attention as _paged_attn

    p, group = layer.p, _MIXERS[layer.kind].group
    k_pool, v_pool = cache.get(group)
    wb, ws = cache.where[group]
    xa = xn.astype(cfg.jdtype)  # the products' operand
    q = _heads(xa, p["wq"], cfg)
    k = _heads(xa, p["wk"], cfg)
    v = _heads(xa, p["wv"], cfg)
    if _rotates(layer.kind, cfg):
        q = _rotate(_rope_window, q, cache.positions, layer.kind, cfg)
        k = _rotate(_rope_window, k, cache.positions, layer.kind, cfg)
    k_pool = k_pool.at[li, wb, ws].set(
        k.reshape(k.shape[:2] + k_pool.shape[3:]).astype(k_pool.dtype),
        mode="drop")
    v_pool = v_pool.at[li, wb, ws].set(
        v.reshape(v.shape[:2] + v_pool.shape[3:]).astype(v_pool.dtype),
        mode="drop")
    cache.set(group, k_pool, v_pool)
    # the layers' pools as one run of pages, a free view: a per-layer
    # slice of a pool would be copied for the kernel
    o = _paged_attn.paged_attention(
        q, k_pool.reshape((-1,) + k_pool.shape[2:]),
        v_pool.reshape((-1,) + v_pool.shape[2:]),
        cache.tables[group] + li * k_pool.shape[1], cache.lengths,
        span=cfg.sliding_window if layer.kind == "sliding" else 0,
        sink=p.get("sink"))
    return _attention_out(o, p, cfg)


def _moe_prefill(xn, layer: _Layer, cache, cfg: TransformerConfig):
    """A layer pattern's expert layer, whose mixer is the FFN itself."""
    return _ffn(xn, layer, cache.valid, cache.counts, cfg), None


def _moe_decode(xn, layer: _Layer, li, cache, cfg: TransformerConfig):
    return _ffn(xn, layer, cache.valid, cache.counts, cfg)


class _Mixer(NamedTuple):
    """A kind of layer's mixer.  ``scopes``: the named scope of its
    prefill half and of its decode half.  ``group``: which arrays of the
    cache it reads and writes (:class:`_Cache`).  ``prefill(xn, layer,
    cache, cfg) -> (addend, what the cache keeps)``, or where ``keys``
    is set (attention) ``keys(xn, layer, cfg) -> kept`` of the whole
    prompt and ``prefill(xn, layer, cfg, kept, first, n_chunks) ->
    addend`` of its rows from ``first``.  ``decode(xn, layer, li, cache,
    cfg) -> addend``, ``li`` the layer's index into the group's arrays
    (a looped model's pass offset added)."""
    scopes: tuple
    group: str
    prefill: Callable
    decode: Callable
    keys: Optional[Callable] = None


def _attention_mixer(scopes, group):
    return _Mixer(scopes, group, _attention_rows, _attention_decode,
                  _attention_keys)


#: every kind of layer's mixer.  The flagship's ("mha") prefill has no
#: scope and its decode is "attention", as traces have always known them
_MIXERS = {
    "mha": _attention_mixer((None, "attention"), "pages"),
    "full": _attention_mixer(("attn_full",) * 2, "pages"),
    "sliding": _attention_mixer(("attn_sliding",) * 2, "ring"),
    "mla": _Mixer(("mla",) * 2, "pages", _mla_prefill, _mla_decode),
    "kda": _Mixer(("kda",) * 2, "state", _kda_prefill, _kda_decode),
    "mamba": _Mixer(("mamba",) * 2, "state", _mamba_prefill, _mamba_decode),
    "moe": _Mixer((None, None), "", _moe_prefill, _moe_decode),
}


def _wide_scopes(cfg: TransformerConfig) -> bool:
    """Whether a mixer's scope holds the layer's first norm and the
    addend's join too (as the programs of the families without
    recurrent layers always had it), or the mixer alone."""
    return all(_MIXERS[kind].group != "state" for kind in cfg.layer_kinds)


def _write_prompt_pages(pool, layers, block_ids, a, rows: bool = False):
    """K or V of ONE sequence's whole blocks ``a [..., 1, n x bs, H_kv,
    hd]`` into pages ``block_ids`` of pool layer(s) ``layers``.  With
    ``rows`` each page is written as ONE row of ``bs x H_kv x hd``
    values (a free view of the pool): Nemotron-H's page of 2 K/V heads
    ``[bs, 2, hd]`` is tiled (2, 128), and for a scatter of whole
    ``[bs, H_kv, hd]`` windows the chip's compiler laid the pool out
    anew, into and out of every prefill (0.6 GB of copies)."""
    if not rows:
        return pool.at[layers, block_ids].set(_paged(a, pool))
    n_layers, n_blocks = pool.shape[:2]
    flat = pool.reshape(n_layers, n_blocks, -1)
    flat = flat.at[layers, block_ids].set(
        a.reshape(a.shape[:-4] + (block_ids.shape[0], -1)).astype(pool.dtype))
    return flat.reshape(pool.shape)


def _write_prompt_rows(pool, layer: int, block_ids, row):
    """A whole prompt's latent rows ``row`` [1, T, r] into pages
    ``block_ids`` of one layer, each page ``[r, slot]``."""
    bs = pool.shape[3]
    return pool.at[layer, block_ids].set(jnp.swapaxes(
        row.reshape(-1, bs, row.shape[-1]), 1, 2).astype(pool.dtype))


def _window_at(pool, table, lengths, pos_w, ring: bool, latent: bool):
    """Where a decode window's entries go in ``pool`` (a K/V pool, whose
    page is ``[block_size, ...]``, or the ``latent`` pool's ``[row,
    block_size]``): ``(blocks [B, S], slots [B, S])`` of positions
    ``pos_w`` [B, S] through ``table`` [B, W], a ring's modulo its
    width; a dead row (length 0) gets a block past the pool, which
    drops its write."""
    n_blocks = pool.shape[1]
    bs = pool.shape[3] if latent else pool.shape[2]
    lb = pos_w // bs
    lb = lb % table.shape[1] if ring else jnp.clip(lb, 0, table.shape[1] - 1)
    wb = jnp.take_along_axis(table, lb, axis=1)
    return jnp.where(lengths[:, None] > 0, wb, n_blocks), pos_w % bs


class _Cache:
    """The paged cache as one serving program holds it, and what
    addresses it.  ``arrays`` is ``PagedKVCache.device_pools()`` in its
    order, by group: "pages" (the K and V pools, or the one latent
    pool), "ring" (the sliding layers' K and V pools) and "state" (the
    recurrent state and its convolution tails), each where the model
    has such layers.  A program's ``extra`` argument is the ring's
    entries (a prefill) or tables (decode), or the rows' state slots.
    Without arrays a prefill's K/V are only kept."""

    def __init__(self, cfg: TransformerConfig, pools=(), extra=()):
        self.cfg = cfg
        self.arrays = list(pools)
        groups = {_MIXERS[kind].group for kind in cfg.layer_kinds}
        self.groups, at = {}, 0
        for group, n in (("pages", 1 if cfg.latent else 2), ("ring", 2),
                         ("state", 2)):
            if group == "pages" or group in groups:
                self.groups[group] = slice(at, at + n)
                at += n
        self.ring = extra[0] if "ring" in self.groups else None
        self.slots = extra[0] if "state" in self.groups else None
        self.kept = {}       # K/V a one-pass prefill writes after its walk
        self.counts = []     # the held experts' routing counts
        self.first = 0       # a looped model's pass offset
        self.at = {}         # each kind's next cache layer in a pass
        self.valid = self.positions = self.lengths = self.live = None
        self.block_ids = self.last_index = None
        self.tables, self.where = {}, {}

    def get(self, group):
        return tuple(self.arrays[self.groups[group]])

    def set(self, group, *arrays):
        self.arrays[self.groups[group]] = arrays

    def moe(self):
        """The program's routing counts ``([n, n_experts + 2],)`` where
        the model holds experts (none under a pattern cut before its
        first expert layer), else ``()``."""
        if self.cfg.moe_router != "sigmoid":
            return ()
        return (jnp.stack(self.counts) if self.counts else jnp.zeros(
            (0, self.cfg.n_experts + 2), jnp.int32),)

    def keep(self, layer: _Layer, kept):
        """What a prefill mixer leaves in the cache: a latent layer's
        rows into its pages, a recurrent layer's final state and tail
        into the sequence's slot, an attention layer's K/V into the
        pages of this pass (a looped model), or after the walk
        (:meth:`flush`)."""
        if kept is None:  # a decode step's mixers write their own
            return
        group = _MIXERS[layer.kind].group
        if group == "state":
            state, tails = self.get("state")
            s_t, tail = kept
            state = state.at[layer.i, self.slots[0]].set(s_t)
            tails = tails.at[layer.i, self.slots[0]].set(
                tail.reshape(tails.shape[2:]).astype(tails.dtype))
            self.set("state", state, tails)
        elif self.cfg.latent:
            pool, = self.get("pages")
            self.set("pages", _write_prompt_rows(pool, layer.i,
                                                 self.block_ids, kept))
        elif self.cfg.loop_passes > 1:
            self.set(group, *(
                _write_prompt_pages(pool, self.first + layer.i,
                                    self.block_ids, a)
                for pool, a in zip(self.get(group), kept)))
        else:
            self.kept.setdefault(group, []).append(kept)

    def flush(self):
        """The kept K/V into their pools, one write a pool: every page
        layer's at ``block_ids``, and the last blocks of every sliding
        layer's into its ring entries (``extra``, in logical order: no
        later query's window reaches further back)."""
        for group in ("pages", "ring"):
            kept = self.kept.get(group)
            if not kept:
                continue
            pools = []
            for pool, a in zip(self.get(group), zip(*kept)):
                a, ids = jnp.stack(a), self.block_ids
                if group == "ring":
                    ids = self.ring
                    a = a[:, :, a.shape[2] - ids.shape[0] * pool.shape[2]:]
                pools.append(_write_prompt_pages(
                    pool, slice(None), ids, a, rows=_one_sublayer(self.cfg)))
            self.set(group, *pools)
        return tuple(self.arrays)


def _prefill_attention_layer(x, layer: _Layer, cache, cfg: TransformerConfig):
    """An attention layer over a whole prompt ``x`` [B, T, E]: its keys
    and values of every row, kept, then the rows through the layer.  A
    prompt longer than :data:`PREFILL_ROWS` (a multiple of it) is walked
    in chunks of rows against the whole K and V."""
    b, t, e = x.shape
    mixer = _MIXERS[layer.kind]
    scope = mixer.scopes[0]
    n_chunks = t // PREFILL_ROWS if (
        cfg.served_only and t > PREFILL_ROWS and t % PREFILL_ROWS == 0) else 1
    rows = t // n_chunks
    norm = "ln" if _one_sublayer(cfg) else "ln1"
    xn = _norm(x, layer.p[norm], cfg)
    with _scope(scope):
        kv = mixer.keys(xn, layer, cfg)

    def some_rows(x, xn, valid, first):
        """Rows [first, first + rows) of the prompt through the layer."""
        counts = []
        with _scope(scope):
            y = mixer.prefill(xn, layer, cfg, kv, first, n_chunks)
            x, y = _mixer_addend(x, y, layer.p, cfg)
        if not _one_sublayer(cfg):
            x = _ffn_half(x, xn, y, layer, valid, counts, cfg)
        return x, counts

    if n_chunks == 1:
        x, counts = some_rows(x, xn, cache.valid, 0)
        cache.counts.extend(counts)
        cache.keep(layer, kv)
        return x

    def chunked(a):  # [B, T, ...] -> [n_chunks, B, rows, ...]
        return jnp.moveaxis(a.reshape((b, n_chunks, rows) + a.shape[2:]), 1, 0)

    def one(args):
        # the chunk's rows normed again: the whole prompt's, which K
        # and V were made of, need not outlive them
        i, x_c, valid_c = args
        return some_rows(x_c, _norm(x_c, layer.p[norm], cfg), valid_c,
                         i * rows)

    valid = jnp.ones((b, t), bool) if cache.valid is None else cache.valid
    x, counts = lax.map(one, (jnp.arange(n_chunks), chunked(x),
                              chunked(valid)))
    cache.counts.extend(c.sum(axis=0) for c in counts)
    cache.keep(layer, kv)
    return jnp.moveaxis(x, 0, 1).reshape(b, t, e)


def _layer(x, layer: _Layer, cache, cfg: TransformerConfig, direction: int,
           mix):
    """A layer: its mixer (``mix(xn) -> (addend, what the cache keeps)``,
    ``direction`` 0 a prefill's, 1 a decode step's), what the mixer
    leaves in the cache, and its FFN half (the held experts open their
    own scope, "moe")."""
    scope, wide = _MIXERS[layer.kind].scopes[direction], _wide_scopes(cfg)
    with _scope(scope if wide else None):
        xn = _norm(x, layer.p["ln" if _one_sublayer(cfg) else "ln1"], cfg)
        with _scope(None if wide else scope):
            y, kept = mix(xn)
            if not wide:
                cache.keep(layer, kept)
        x, y = _mixer_addend(x, y, layer.p, cfg)
        if wide:
            cache.keep(layer, kept)
    if not _one_sublayer(cfg):
        with _scope("mlp" if layer.first_group is None and not cfg.latent
                    and direction else None):
            x = _ffn_half(x, xn, y, layer, cache.valid, cache.counts, cfg)
    return x


def _walk(params, x, cache, step, cfg: TransformerConfig):
    """``x`` through ``step(x, layer) -> x`` for every layer, in order:
    once, or once a pass of a looped model (:func:`_loop_passes`, which
    closes each pass with the final norm), pass t's layer l at cache
    layer ``t * n_layers + l`` (``cache.first`` the pass's offset)."""
    def one_pass(first, x, arrays):
        cache.arrays, cache.first, cache.at = list(arrays), first, {}
        for layer in _serving_layers(params, cfg):
            x = step(x, layer)
        return x, tuple(cache.arrays)

    if cfg.loop_passes > 1:
        x, arrays = _loop_passes(x, tuple(cache.arrays), one_pass, params,
                                 cfg)
    else:
        x, arrays = one_pass(0, x, tuple(cache.arrays))
    cache.arrays = list(arrays)
    return x


def _prefill_walk(params, ids, cache, cfg: TransformerConfig):
    """Every layer over a whole prompt ``ids`` [B, T], writing or
    keeping what the cache holds of it: the hidden states [B, T, E]
    after the final norm."""
    t = ids.shape[1]
    if cfg.moe_router == "sigmoid" or cache.slots is not None:
        # the prompt's real tokens: what the routing counts and the
        # recurrences take in
        cache.positions = jnp.arange(t)[None]
        cache.valid = cache.positions <= cache.last_index[:, None]
    x = embed_lookup(params["embed"], ids, ShardAxes()).astype(
        cfg.stream_dtype)

    def step(x, layer):
        mixer = _MIXERS[layer.kind]
        if mixer.keys is not None:
            return _prefill_attention_layer(x, layer, cache, cfg)
        return _layer(x, layer, cache, cfg, 0,
                      lambda xn: mixer.prefill(xn, layer, cache, cfg))

    x = _walk(params, x, cache, step, cfg)
    return x if cfg.loop_passes > 1 else _norm(x, params["ln_f"], cfg)


def prefill_paged(params, ids, last_index, pools, block_ids, extra,
                  cfg: TransformerConfig):
    """Prefill of ONE sequence of any family that leaves what the cache
    keeps of it on the device: ``(logits [1, V], pools, moe)``.

    ids [1, T] with T a whole number of blocks (the engine pads prompts
    so); ``last_index`` [1] its last real token; ``pools`` the cache's
    arrays (``PagedKVCache.device_pools()``, donated and updated in
    place); ``block_ids`` [T / block_size] int32, the sequence's block
    table: logical block j of every layer lands in physical block
    ``block_ids[j]``, the layout :func:`decode_paged` reads.  ``extra``
    is ``()``, the ring entries a sliding layer's last blocks go to
    (``PagedKVCache.sliding_prefill_ids``), or the sequence's state slot
    [1].  Slots of the last block past the prompt's true length hold
    the pad tokens' K/V: nothing reads a slot at or past a row's
    length, and decode overwrites each before the length passes it; a
    pad token neither decays nor writes a recurrent state.  ``moe``:
    ``(counts [n expert layers, n_experts + 2],)`` of the prompt's real
    tokens where the model holds experts (:func:`_moe_held_ffn`), else
    ``()``."""
    cache = _Cache(cfg, pools, extra)
    cache.block_ids, cache.last_index = block_ids, last_index
    x = _prefill_walk(params, ids, cache, cfg)
    pools = cache.flush()
    return _logits_at(params, x, last_index, cfg), pools, cache.moe()


def decode_paged(params, ids, positions, pools, block_tables, lengths,
                 extra, cfg: TransformerConfig):
    """Decode window step of any family against the cache IN PLACE:
    ``(logits [B, S, V], pools, moe)``.

    ids / positions [B, S] (S=1 plain decode, S=k+1 speculative verify;
    a ring or recurrent state holds one token's reach, so S=1 there);
    ``pools`` the cache's arrays (donated; each layer writes the
    window's entries at ``lengths[b] + s`` and then reads them, so the
    write is in place); block_tables [B, W] int32 (rows padded with 0);
    lengths [B] int32 committed tokens per row, a dead row's 0 (it
    writes nothing and is not counted); ``extra`` ``()``, the rows' ring
    tables [B, R] (sliding layers) or state slots [B] (recurrent
    layers).  ``moe`` as :func:`prefill_paged`'s, of the live rows."""
    b, s_w = ids.shape
    cache = _Cache(cfg, pools, extra)
    assert s_w == 1 or set(cache.groups) == {"pages"}, \
        "a ring or a recurrent state holds one decode token's reach"
    if cache.slots is not None:
        cache.live = lengths > 0
    cache.positions, cache.lengths = positions, lengths
    cache.tables = {"pages": block_tables}
    if cache.ring is not None:
        cache.tables["ring"] = cache.ring
    pos_w = lengths[:, None] + jnp.arange(s_w)[None, :]          # [B, S]
    for group, table in cache.tables.items():
        cache.where[group] = _window_at(cache.get(group)[0], table, lengths,
                                        pos_w, group == "ring", cfg.latent)
    cache.valid = cache.live[:, None] if cache.live is not None \
        else jnp.broadcast_to(lengths[:, None] > 0, (b, s_w))
    x = embed_lookup(params["embed"], ids, ShardAxes()).astype(
        cfg.stream_dtype)

    def step(x, layer):
        # a kind's layer l at its cache layer first + l
        li = cache.at.get(layer.kind, cache.first)
        cache.at[layer.kind] = li + 1
        mixer = _MIXERS[layer.kind]
        return _layer(x, layer, cache, cfg, 1, lambda xn: (
            mixer.decode(xn, layer, li, cache, cfg), None))

    x = _walk(params, x, cache, step, cfg)
    with jax.named_scope("unembed"):
        if cfg.loop_passes == 1:  # a looped model's passes end normed
            x = _norm(x, params["ln_f"], cfg)
        logits = _unembed(params, x, cfg)
    return logits, tuple(cache.arrays), cache.moe()


#: the names a trace knows each family's two serving programs by
#: (``jit(<name>)``), which the benchmark's trace readers match
PROGRAM_NAMES = {
    "mha": ("forward_prefill_paged", "forward_decode_paged"),
    "mha_swa": ("forward_prefill_paged_swa", "forward_decode_paged_swa"),
    "mla": ("forward_prefill_paged_mla", "forward_decode_paged_mla"),
    "kda_mla": ("forward_prefill_paged_hybrid", "forward_decode_paged_hybrid"),
    "nemotron_h": ("forward_prefill_paged_pattern",
                   "forward_decode_paged_pattern"),
}


def make_train_step(mesh, cfg: TransformerConfig, optimizer=None,
                    ledger: bool = True, grad_norm: bool = False,
                    overlap: Optional[str] = None):
    """Build a jitted SPMD train step over ``mesh``.

    Returns (train_step, init_state) where
      train_step(params, opt_state, ids, labels) -> (params, opt_state, loss)
    ids/labels are global [B, T] arrays sharded P(dp, sp).

    ``overlap="device"`` swaps the fused (dp, sp) gradient reduction
    the loss-pmean transpose produces — one big psum at the very end of
    backward, fully exposed — for one ``lax.psum`` per reverse-
    topological gradient bucket (``DMLC_COLL_BUCKET_MB``,
    parallel.overlap.bucketed_psum_mean), issued as soon as backward
    can produce the bucket: XLA's latency-hiding scheduler then starts
    the first buckets' ICI/DCN traffic while earlier layers are still
    differentiating and the optimizer update runs.  Numerically the
    same psum-then-divide in the same cross-replica order, so the loss
    trajectory is unchanged.  Default (None, or ``DMLC_COLL_OVERLAP=0``
    with "auto") keeps the classic fused path.

    With ``ledger`` (default) every call drives the process step ledger
    (telemetry.steps): the model declares its per-token train FLOPs
    from the first batch's sequence length, and each step records wall
    time, feed/collective attribution, goodput, and MFU — the data the
    tracker watchdog and ``dmlc top`` read.  Wall time is host dispatch
    time; under steady-state async dispatch that converges to device
    step time (the dispatch queue is device-throttled).

    With ``grad_norm`` the step additionally returns the global L2 norm
    of the gradients as a fourth output — one scalar that goes
    non-finite whenever ANY gradient does, which is what the self-heal
    guard (resilience.selfheal) checks per step: a NaN that has not yet
    reached the loss is caught before the optimizer commits it.
    """
    import optax

    if overlap == "auto":
        from ..base import get_env

        overlap = "device" if get_env("DMLC_COLL_OVERLAP", False) \
            else None
    if overlap not in (None, "device"):
        raise ValueError(f"unknown overlap mode {overlap!r} "
                         "(expected None, 'device' or 'auto')")
    if optimizer is None:
        optimizer = optax.adamw(1e-3)
    specs = param_specs()
    data_spec = P(AXIS_DP, AXIS_SP)

    if overlap == "device":
        from ..parallel.overlap import bucketed_psum_mean

        data_axes = tuple(a for a in (SHARDED_AXES.dp, SHARDED_AXES.sp)
                          if a is not None)

        def _local_overlap(p, i, l):
            loss, grads = jax.value_and_grad(
                lambda pp_: forward_local(pp_, i, l, cfg, SHARDED_AXES,
                                          reduce_loss=False)
            )(p)
            # the explicit bucketed psums replace the loss-pmean
            # transpose's single fused end-of-backward reduction
            grads = bucketed_psum_mean(grads, data_axes)
            loss = lax.pmean(loss, data_axes)
            return loss, grads

        local = jax.shard_map(
            _local_overlap,
            mesh=mesh,
            in_specs=(specs, data_spec, data_spec),
            out_specs=(P(), specs),
        )
    else:
        local = jax.shard_map(
            lambda p, i, l: jax.value_and_grad(
                lambda pp_: forward_local(pp_, i, l, cfg, SHARDED_AXES)
            )(p),
            mesh=mesh,
            in_specs=(specs, data_spec, data_spec),
            out_specs=(P(), specs),
        )

    def train_step(params, opt_state, ids, labels):
        loss, grads = local(params, ids, labels)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        if grad_norm:
            return params, opt_state, loss, optax.global_norm(grads)
        return params, opt_state, loss

    def init_state(params):
        return optimizer.init(params)

    from ..telemetry import compute as _compute

    # profiled_jit is plain jax.jit when DMLC_COMPUTE_PROFILE=0; when
    # on it counts traces vs cache hits per call signature (recompile
    # ledger) and extracts the executable's XLA cost analysis
    jitted = _compute.profiled_jit(train_step, site="train.step")
    if not ledger:
        return jitted, init_state

    from .. import telemetry

    declared = []

    def stepped(params, opt_state, ids, labels):
        if not declared:
            telemetry.declare_flops_per_token(
                train_flops_per_token(cfg, int(ids.shape[-1])))
            telemetry.declare_dtype(cfg.dtype)
            declared.append(True)
        telemetry.step_begin()
        # a raising dispatch leaves the step open; the next step_begin
        # abandons it instead of recording a garbage wall time
        out = jitted(params, opt_state, ids, labels)
        stats_fn = getattr(jitted, "stats", None)  # absent on plain jit
        cost = stats_fn().get("last_cost") if stats_fn else None
        telemetry.step_end(
            tokens=float(ids.size),
            bytes_accessed=cost.get("bytes_accessed") if cost else None)
        if _compute.enabled():
            _compute.sample_hbm()
        return out

    return stepped, init_state
