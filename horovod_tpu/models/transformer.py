"""Llama-family decoder LM, written for the TPU mesh from day one.

Design (vs. the reference, which has no model code of its own and rides
torchvision/Keras — SURVEY.md §6):

* **Pure-functional params pytree** with per-layer leaves *stacked* on a
  leading ``n_layers`` dim and a ``lax.scan`` over layers: one layer's
  HLO compiled once regardless of depth (compile-time and code-size
  friendly, the standard JAX LM idiom).
* **Megatron-style tensor parallelism by annotation**: attention heads
  and FFN hidden dim sharded over ``tp``; GSPMD inserts the psum pair
  per block. No hand-written collective calls in the model body.
* **FSDP by annotation**: the non-tp dim of every matrix is sharded over
  ``fsdp``; XLA all-gathers params on use and reduce-scatters grads —
  the ZeRO-3 pattern the reference approximates with
  reduce-scatter+allgather hierarchical allreduce
  (``nccl_operations.cc:187-360``).
* **Sequence parallelism**: activations' ``T`` dim sharded over ``sp``;
  attention runs as a ring-attention ``shard_map`` island
  (:mod:`horovod_tpu.parallel.ring_attention`) — manual over ``sp``
  only, GSPMD elsewhere.
* bf16 params/activations, f32 RMSNorm accumulation and loss, RoPE, GQA,
  SwiGLU — Llama-3 shapes supported directly.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.models import moe as moe_lib
from horovod_tpu.parallel.ring_attention import make_sp_attention


@dataclasses.dataclass(frozen=True)
class Rotary:
    """How one kind of layer rotates q and k: plainly, pair i at the
    frequency ``theta^(-2i/d)``, or, with a ``factor``, by YaRN (Peng et
    al. 2023, as ``transformers`` computes it): pairs that turn more
    than ``beta_fast`` times within ``original_max_seq`` positions keep
    that frequency, those that turn fewer than ``beta_slow`` times take
    it divided by ``factor``, a linear ramp between the two; cos and sin
    times ``attention_factor``, so on q and on k alike. The frequencies
    are fixed, whatever the row's length. ``mscale_all_dim`` is
    DeepSeek-V3's: the softmax scale of a layer rotated so is times
    ``(0.1 mscale_all_dim ln(factor) + 1)^2`` (``softmax_mscale``; the
    mla layers read it, 0 leaves the scale alone)."""
    theta: float
    factor: Optional[float] = None
    original_max_seq: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0
    mscale_all_dim: float = 0.0

    @property
    def softmax_mscale(self) -> float:
        if not self.mscale_all_dim or not self.factor or self.factor <= 1:
            return 1.0
        return (0.1 * self.mscale_all_dim * math.log(self.factor) + 1.0) ** 2

    def frequencies(self, d: int) -> np.ndarray:
        """The ``d / 2`` pairs' angles a position, float32."""
        plain = self.theta ** -(np.arange(0, d, 2, dtype=np.float64) / d)
        if self.factor is None:
            return plain.astype(np.float32)

        def pair_turning(times):    # the pair that turns `times` times
            return d * math.log(self.original_max_seq
                                / (2 * math.pi * times)) / (
                                    2 * math.log(self.theta))

        low = max(math.floor(pair_turning(self.beta_fast)), 0)
        high = min(math.ceil(pair_turning(self.beta_slow)), d - 1)
        ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3),
                       0.0, 1.0)
        return (plain / self.factor * ramp
                + plain * (1 - ramp)).astype(np.float32)


#: The kinds of layer a stack with ``layer_types`` may hold.
LAYER_KINDS = ("sliding", "full", "kda", "mla", "mamba", "sparse",
               "lightning", "conv", "eva", "mamba2", "mla_sliding")
#: The two kinds whose cache is a latent (pages, or a ring by slot).
MLA_KINDS = ("mla", "mla_sliding")
#: What ``layer_types`` names a layer that is a feed-forward ALONE, in a
#: stack whose layers are one branch each (``one_branch``): no kind of
#: mixer, and nothing kept between calls.
FFN = "ffn"


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32_000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 8          # < n_heads → GQA
    d_ff: int = 1376             # SwiGLU hidden
    max_seq: int = 2048
    rope_theta: float = 500_000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16    # params/activations; reductions in f32
    remat: bool = True           # jax.checkpoint each layer (HBM for FLOPs)
    # "dots": save matmul outputs, recompute elementwise (measured ~9%
    # faster than full recompute at d=2048 on v5e); "full": recompute
    # everything (minimum memory).
    remat_policy: str = "dots"
    sp_attention: str = "ring"   # "ring" | "ulysses" | "local" |
                                 # "flash" (Pallas kernel, sp=1) |
                                 # "ring_flash" (Pallas blocks, sp>1)
    # Pallas flash tile sizes (None = derived from the sequence
    # length: sequence-spanning up to 1024 through seq 4096, 512x1024
    # beyond — see ops/flash_attention._default_blocks for the
    # measurements). Explicit values override the derivation.
    flash_block_q: Optional[int] = None
    flash_block_k: Optional[int] = None
    # Layer-scan unroll factor: unrolling lets XLA overlap across layer
    # boundaries (+2-3 MFU points at 8 layers); 1 = rolled (smallest
    # program, fastest compile — the multichip/pp paths keep 1).
    scan_unroll: int = 1
    # jax.checkpoint(prevent_cse=...): False is safe under scan/jit
    # (per the JAX docs) and measures +4 MFU points; True is the
    # conservative default only for historical reasons.
    remat_prevent_cse: bool = False
    # Mixture-of-Experts: n_experts > 0 replaces the dense SwiGLU FFN
    # with an expert-parallel MoE FFN in every layer (experts sharded
    # over the `ep` mesh axis; see models/moe.py).
    # With n_experts > 0, d_ff is ONE expert's width.
    n_experts: int = 0
    moe_top_k: int = 2
    # None: no capacity and no dropped token — the sorted, dropless
    # dispatch of models/moe.py (chosen by the code from this field).
    moe_capacity_factor: Optional[float] = 1.25
    # False: the chosen gates are used as the softmax gave them
    # (OLMoE's norm_topk_prob); True renormalises them to sum to 1.
    moe_norm_topk_prob: bool = True
    # Coefficients of the two router losses lm_loss adds to the
    # cross-entropy: load balancing, and the z-loss
    # mean(logsumexp(router logits)²); each is summed over layers.
    moe_aux_loss_coef: float = 0.01
    moe_z_loss_coef: float = 0.0
    # RMSNorm with a learned weight over the whole projected q vector
    # and the whole projected k vector, before the split into heads
    # and the rotary embedding (OLMoE, OLMo-2).
    qk_norm: bool = False
    # MoE dispatch plane (ISSUE 18): None defers to the
    # HOROVOD_MOE_DISPATCH / HOROVOD_MOE_COMPRESSION env knobs
    # (docs/perf_tuning.md). "island" + a lossy codec routes the
    # dispatch/combine hops through the quantized-alltoall shard_map
    # island in models/moe.py; "gspmd" (the default) or codec "none"
    # keep the exact pre-existing GSPMD einsum path.
    moe_dispatch: Optional[str] = None
    moe_compression: Optional[str] = None
    # Layers of more than one kind in one stack (ISSUE 32; the defaults
    # describe one block repeated, as every field above does).
    # A head size that is not d_model / n_heads (None: it is).
    d_head: Optional[int] = None
    # The first n_dense_layers layers keep a dense SwiGLU of width
    # d_ff_dense while the rest are MoE layers (n_experts > 0): the
    # parameters are then two lists of layers, params["dense_layers"]
    # and params["layers"] (see `mixed`).
    n_dense_layers: int = 0
    d_ff_dense: Optional[int] = None
    # One of the LAYER_KINDS a layer ("sliding" | "full" here; "kda",
    # "mla", "mamba", "sparse", "lightning", "conv", "eva", "mamba2" and
    # "mla_sliding" below; "ffn" with one_branch). A
    # sliding layer sees the keys
    # j with p - attn_window < j <= p and rotates q and k; a full layer
    # sees every j <= p and applies no rotary embedding, unless
    # layer_rotary says otherwise. None: every layer is causal over
    # everything and rotates.
    layer_types: Optional[Tuple[str, ...]] = None
    attn_window: Optional[int] = None
    # How a kind of layer rotates, where it is not as above: a mapping
    # (a configuration file's object) or pairs from "sliding" | "full"
    # to a Rotary, to its fields, or to None for no rotary embedding
    # (see `rotary_of`).
    layer_rotary: Optional[Tuple[Tuple[str, Optional[Rotary]], ...]] = None
    # RMSNorm with a gain of size head_dim over each head of q and k.
    qk_norm_per_head: bool = False
    # a <- a * sigmoid(u Wg) on the attention's output, before wo;
    # u the layer's normed input, Wg [D, H * Dh].
    attn_gate: bool = False
    # A second RMSNorm on each branch's output, before the residual.
    sandwich_norm: bool = False
    # Embeddings times sqrt(d_model).
    embed_scale: bool = False
    # Router scoring "softmax" | "sigmoid" (models/moe.py); the chosen
    # gates times moe_route_scale; one shared SwiGLU of width d_ff
    # beside the routed sum.
    moe_scoring: str = "softmax"
    moe_route_scale: float = 1.0
    moe_shared_expert: bool = False
    # One chip's share of the experts: the router scores all n_experts,
    # the layer holds and runs experts [offset, offset + held) and adds
    # nothing for the others (None: all of them).
    moe_experts_held: Optional[int] = None
    moe_expert_offset: int = 0
    # Group-limited routing (models/moe.py: MoEConfig.n_group).
    moe_n_group: int = 1
    moe_topk_group: int = 1
    # Two further kinds of layer_types, whose state is not cached keys
    # (ISSUE 38; the serve programs run them, the trainer refuses):
    # "kda", Kimi Delta Attention: q, k, v of n_heads x head_dim through
    # a causal depthwise convolution of kda_conv taps and SiLU, a
    # delta-rule recurrence over a state [n_heads, head_dim, head_dim] a
    # sequence whose decay a channel is exp(kda_decay_floor *
    # sigmoid(.)), a gated per-head RMSNorm on its output;
    # "mla", DeepSeek-V2's latent attention: keys and values are
    # expanded from a cached latent of mla_kv_rank values a position
    # beside mla_rope_dim rotated ones that every head shares (q and k
    # are head_dim + mla_rope_dim wide, v head_dim), rotated in
    # interleaved pairs as layer_rotary says of "mla" (plainly at
    # rope_theta where it says nothing). mla_q_rank: q comes up from a
    # normed latent of that many values (DeepSeek-V3's q_lora_rank; 0:
    # straight out of one matrix). mla_head_gate: one sigmoid gate a
    # head on the attention's output (Ling's; DeepSeek-V3 has none).
    kda_conv: int = 4
    kda_decay_floor: float = -5.0
    mla_kv_rank: int = 0
    mla_rope_dim: int = 0
    mla_q_rank: int = 0
    mla_head_gate: bool = True
    # A fifth kind of layer_types (ISSUE 47; served, not trained):
    # "mamba", Mamba-1's selective state-space layer: the normed input
    # up to 2 * mamba_expand * d_model (u | z), u through a causal
    # depthwise convolution of mamba_d_conv taps with a bias and SiLU,
    # then (delta | B | C) of mamba_dt_rank | mamba_d_state |
    # mamba_d_state values, each RMS-normed with a gain of its own,
    # Delta = softplus(delta W_dt + b_dt), and the recurrence
    # s_t = exp(Delta_t A) s_{t-1} + (Delta_t u_t) B_t over a float32
    # state [mamba_expand * d_model, mamba_d_state] a sequence,
    # y_t = s_t C_t + D u_t, gated by SiLU(z). It reads no position.
    # The full layers beside it may be multi-query (n_kv_heads <
    # n_heads).
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0
    # The head is the embedding's transpose: logits = x E^T, and the
    # parameters hold no "lm_head" (a configuration with layer_types:
    # forward_with_aux and decode.py's mixed_programs read
    # head_weights; the programs of one stack of one block take a
    # separate lm_head).
    tie_embeddings: bool = False
    # A sixth and a seventh kind of layer_types (ISSUE 50; served, not
    # trained). "sparse", InfLLM-v2's attention (MiniCPM4): a full
    # layer's q, k, v, norms and gate, and beside the keys their means
    # over kernels of sparse_kernel positions every sparse_stride (the
    # COMPRESSED keys). A query at a position >= sparse_dense_len scores
    # the kernels that are complete at its position (a softmax over
    # them a head), a block of sparse_block positions by the largest
    # score of a kernel that meets it, summed over the heads of the
    # query's GQA group; the group attends the keys of its sparse_topk
    # best blocks alone, the first sparse_init_blocks and the blocks
    # that hold the sparse_window positions before the query always
    # among them. A query below sparse_dense_len attends every key
    # before it. "lightning", Lightning Attention's linear recurrence:
    # q, k, v of n_heads x head_dim each (as many heads for k and v as
    # for q, whatever n_kv_heads the sparse layers beside it have), q
    # and k normed a head where qk_norm_per_head and rotated (in
    # halves, at rope_theta), a float32 state S [heads, Dh, Dh] a
    # sequence, S_t = lambda_h S_{t-1} + k_t^T v_t with the head's
    # fixed decay lambda_h = exp(-2^(-8 (h + 1) / heads)), o_t =
    # (q_t / sqrt(Dh)) S_t, an RMSNorm over all heads' outputs together
    # and the gate of attn_gate.
    sparse_kernel: int = 32
    sparse_stride: int = 16
    sparse_block: int = 64
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_window: int = 2048
    sparse_dense_len: int = 8192
    # MiniCPM's three multipliers (None: none): the embeddings times
    # embed_multiplier, every residual branch (mixer and feed-forward)
    # times residual_multiplier, the head's input divided by
    # logit_divisor.
    embed_multiplier: Optional[float] = None
    residual_multiplier: Optional[float] = None
    logit_divisor: Optional[float] = None
    # An eighth kind of layer_types (ISSUE 54; served, not trained):
    # "conv", LFM2's gated short convolution: the normed input up to
    # 3 * d_model (B | C | u), z = B * u through a causal depthwise
    # convolution of conv_taps taps a channel with no bias and no
    # activation, times C, and down through one more matrix. Between
    # calls a sequence keeps the newest conv_taps - 1 rows of z and
    # nothing else: no recurrence. It reads no position. The full layers
    # beside it keep their own n_kv_heads, qk_norm_per_head and
    # layer_rotary.
    conv_taps: int = 3
    # A ninth kind of layer_types (ISSUE 56; served, not trained):
    # "eva", EvaByte's attention (EVA, Zheng et al. 2023, in the form of
    # its modelling code): a full layer's q, k and v (rotated in HALVES
    # at rope_theta), and two learned vectors a KV head, eva_mu and
    # eva_phi [Hkv, Dh]. Positions fall into chunks of eva_chunk and ALIGNED
    # windows of eva_window (whole chunks); a chunk's summary, once its
    # positions exist, is (sum_j softmax_j(s mu.k_j) k_j, sum_j
    # softmax_j(s phi.k_j) v_j), s = Dh^-1/2. The query at t attends, in
    # ONE float32 softmax, the keys of its own window up to itself
    # exactly and one summary a chunk of every window that has closed.
    # It is the one kind with both halves between calls: the open
    # window's K and V rows by batch slot, and the summaries in pages
    # behind the block tables (kv_cache.KVCache).
    eva_window: int = 2048
    eva_chunk: int = 16
    # EvaByte's three further switches, each read in one place. The
    # norms multiply by 1 + g, g the stored gain (norm_unit_offset: the
    # stored g in bf16 plus one is not a stored 1 + g in bf16, so it is
    # never folded into the weights). The stream between the layers,
    # both residual additions and the head's logits are float32
    # (stream_fp32: the norms read float32 and give `dtype`). The head
    # predicts head_rows positions a position: lm_head is [d_model,
    # head_rows * vocab_size], logits [.., head_rows, vocab_size], row j
    # the token j + 1 ahead; the serve programs emit from row 0.
    norm_unit_offset: bool = False
    stream_fp32: bool = False
    head_rows: int = 1
    # A tenth kind of layer_types (ISSUE 60; served, not trained):
    # "mamba2", Mamba-2's state-space duality (SSD) layer. Of the normed
    # input ``[z | xBC | dt] = h W_in`` as ``Di | Di + 2 G N | Hm`` (Di =
    # mamba_expand * d_model channels in Hm = Di / mamba2_head_dim heads,
    # N = mamba_d_state, G = mamba2_groups), xBC through a causal
    # depthwise convolution of mamba_d_conv taps with a bias and SiLU,
    # split x [Hm, P] and B, C [G, N] (head h reads group h // (Hm / G));
    # Delta = softplus(dt + dt_bias), ONE scalar decay a head exp(Delta
    # A), a float32 state [Hm, P, N] a sequence, S_t = exp(Delta_t A)
    # S_{t-1} + Delta_t x_t (x) B_t, y_t = S_t C_t + D x_t; the output is
    # gated by SiLU(z) and THEN RMS-normed over each of the G groups of
    # Di / G channels, before W_out. It reads no position. A chunk of a
    # prompt runs as matrix products over blocks of mamba2_chunk
    # positions (serve/decode.py::ssd_scan). mamba2_dt_range: the
    # seeded step's (least, most, floor), Mamba-2's own initialisation.
    mamba2_head_dim: int = 64
    mamba2_groups: int = 1
    mamba2_chunk: int = 128
    mamba2_dt_range: Tuple[float, float, float] = (1e-3, 1e-1, 1e-4)
    # Every layer is ONE branch (Nemotron-H's stack): a layer of a kind
    # of LAYER_KINDS is its mixer alone (one norm, one residual addition,
    # no feed-forward), and a layer that layer_types names "ffn" is a
    # feed-forward alone (the mixture where n_experts > 0), with no
    # mixer and nothing kept between calls. Served, not trained.
    one_branch: bool = False
    # The mixture's experts are UNGATED (moe_activation "relu2": relu(x
    # W1)^2 W2, no gate matrix; "swiglu": the three matrices every other
    # configuration has), run in a latent of moe_latent values (0: at
    # d_model) between one projection down before the dispatch and one up
    # after the routed sum, and the shared expert, on the full-width
    # input, has a width of its own (None: d_ff). The router reads the
    # full-width input whatever the experts read (models/moe.py).
    moe_activation: str = "swiglu"
    moe_latent: int = 0
    moe_shared_d_ff: Optional[int] = None
    # An eleventh kind of layer_types (ISSUE 63; served, not trained):
    # "mla_sliding", an mla layer that sees the keys j with p -
    # attn_window < j <= p: what it keeps of a position is the mla
    # layer's latent row, in a RING by batch slot as a sliding layer's
    # keys are (kv_cache.KVCache) and not in pages. Both mla kinds may
    # have GROUPED heads (Motif's GDLA): n_kv_heads < n_heads key and
    # value heads come up from the latent (w_ukv [C, n_kv_heads * 2 *
    # Dh]) and KV head g serves the query heads g * n_heads / n_kv_heads
    # onwards. mla_noise_heads (0 or n_kv_heads): the LAST query head of
    # each group is a noise head, and each other (signal) head's output
    # is A_s - sigmoid(h W_lambda)_s * A_noise (Differential Transformer
    # V2: lambda a token and signal head, no norm after); wo reads the
    # signal heads alone. mla_elementwise_gate: the output times
    # sigmoid(h Wg) elementwise, Wg [D, signal heads * Dh] (where
    # mla_head_gate is one value a head).
    mla_noise_heads: int = 0
    mla_elementwise_gate: bool = False
    # The residual path of manifold-constrained hyper-connections (mHC,
    # arXiv:2512.24880): the stream is mhc_streams rows a position, [.., n,
    # D]; around each branch F of a layer, with x~ = RMSNorm(vec X) in
    # float32, H_pre = sigmoid(a x~ W_pre + b) [n], H_post = 2 sigmoid(.)
    # [n], H_res = mhc_sinkhorn_iters alternations of row and column
    # normalisation of exp(a reshape(x~ W_res) + b) [n, n]; X' = H_res X
    # + H_post^T F(norm(H_pre X)) (stream_in / stream_out, the serve
    # programs' mla kinds and ffn_block). The embedding is copied to the n
    # streams and their sum goes to the final norm and the head. 1: the
    # one stream every other configuration has.
    mhc_streams: int = 1
    mhc_sinkhorn_iters: int = 20
    # The dense feed-forward's activation: "swiglu", or "polynorm"
    # (models/moe.py::polynorm: s (w1 N(z^3) + w2 N(z^2) + w3 N(z) +
    # clip(b)) on the gate's product z, N the RMS norm over the width,
    # float32, three weights and a bias a feed-forward; moe_activation
    # "polynorm" gives every expert and the shared expert their own).
    ffn_activation: str = "swiglu"
    polynorm_scale: float = 1.0
    polynorm_bias_clamp: float = 0.5

    def __post_init__(self):
        object.__setattr__(self, "mamba2_dt_range",
                           tuple(self.mamba2_dt_range))
        if self.layer_types is not None:
            # a configuration file gives a list; the config is a jit key
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
            if (len(self.layer_types) != self.n_layers
                    or set(self.layer_types) - set(LAYER_KINDS)
                    - ({FFN} if self.one_branch else set())):
                raise ValueError(
                    f"layer_types needs n_layers={self.n_layers} entries "
                    f"of the {len(LAYER_KINDS)} kinds "
                    f"{' | '.join(map(repr, LAYER_KINDS))} (eva alone keeps "
                    "both rows by slot and pages behind the tables), got "
                    f"{self.layer_types}")
            if "sliding" in self.layer_types and not self.attn_window:
                raise ValueError("sliding layers need attn_window")
            if set(MLA_KINDS) & set(self.layer_types) and not (
                    self.mla_kv_rank and self.mla_rope_dim):
                raise ValueError("mla layers need mla_kv_rank and "
                                 "mla_rope_dim")
            if "mla_sliding" in self.layer_types and not self.attn_window:
                raise ValueError("mla_sliding layers need attn_window")
            if "mamba" in self.layer_types and not self.mamba_dt_rank:
                raise ValueError("mamba layers need mamba_dt_rank")
            if "mamba2" in self.layer_types and (
                    self.mamba_expand * self.d_model % self.mamba2_head_dim
                    or self.mamba2_heads % self.mamba2_groups):
                raise ValueError(
                    "mamba2 layers need mamba_expand * d_model in whole "
                    "heads of mamba2_head_dim, and the heads in "
                    f"mamba2_groups equal groups (got {self.mamba_expand} * "
                    f"{self.d_model}, {self.mamba2_head_dim} and "
                    f"{self.mamba2_groups})")
            if "eva" in self.layer_types and (
                    self.eva_chunk < 1 or self.eva_window % self.eva_chunk
                    or self.attn_gate or self.qk_norm):
                raise ValueError(
                    "eva layers need eva_window in whole eva_chunk "
                    f"(got {self.eva_window} and {self.eva_chunk}) and no "
                    "attn_gate, nor a qk_norm over the whole vector (the "
                    "one kind that keeps both rows by slot and summary "
                    "pages behind the tables)")
            # kda and mla layers project their own heads with norms and
            # gates of their own (a kda layer n_heads of each; an mla
            # layer n_heads of q over n_kv_heads of k and v out of the
            # latent); mamba and conv layers have no q or k, and the
            # full layers beside them keep the configuration's
            # n_kv_heads and qk_norm_per_head
            own_heads = {"kda", *MLA_KINDS} & set(self.layer_types)
            no_heads = {"mamba", "conv", "mamba2"} & set(self.layer_types)
            if "sparse" in self.layer_types and (
                    self.sparse_kernel % self.sparse_stride
                    or self.sparse_block % self.sparse_stride
                    or self.sparse_window % self.sparse_block
                    or self.sparse_dense_len % self.sparse_block
                    or self.sparse_init_blocks
                    + self.sparse_window // self.sparse_block
                    > self.sparse_topk):
                raise ValueError(
                    "sparse layers need sparse_kernel and sparse_block in "
                    "whole sparse_stride, sparse_window and sparse_dense_len "
                    "in whole sparse_block, and the forced blocks "
                    "(sparse_init_blocks and the window's) within "
                    "sparse_topk")
            if own_heads and (
                    (self.n_kv_heads != self.n_heads
                     if "kda" in own_heads
                     else self.n_heads % self.n_kv_heads)
                    or self.attn_gate or self.sandwich_norm
                    or self.qk_norm or self.qk_norm_per_head):
                raise ValueError(
                    f"{' and '.join(sorted(own_heads))} layers have n_heads "
                    "heads of their own projections, norms and gates: "
                    "n_kv_heads = n_heads (mla layers alone: n_kv_heads "
                    "dividing n_heads), and no attn_gate, sandwich_norm "
                    "or qk_norm")
            if no_heads and (self.attn_gate or self.sandwich_norm
                             or self.qk_norm):
                # what a mamba or conv layer's own residual does not
                # read, and what nothing served pairs with them
                raise ValueError(
                    f"{' and '.join(sorted(no_heads))} layers have no "
                    "gate and no norm on their branch's output: no "
                    "attn_gate, sandwich_norm or qk_norm over the whole "
                    "vector (qk_norm_per_head and n_kv_heads are the full "
                    "layers' beside them)")
        mla_alone = bool(self.layer_types) and not (
            set(self.layer_types) - set(MLA_KINDS))
        if self.mla_noise_heads and not (
                mla_alone and self.mla_noise_heads == self.n_kv_heads
                and self.n_heads >= 2 * self.n_kv_heads):
            raise ValueError(
                "mla_noise_heads are the mla kinds' (mla, mla_sliding): one "
                "noise head a KV head, the last query head of its group, "
                f"beside at least one signal head (got {self.mla_noise_heads} "
                f"of {self.n_heads} heads over {self.n_kv_heads} KV heads)")
        if self.mla_elementwise_gate and not (
                mla_alone and not self.mla_head_gate):
            raise ValueError(
                "mla_elementwise_gate is the mla kinds' output gate, in "
                "place of mla_head_gate: set one")
        if self.mhc_streams < 1 or self.mhc_sinkhorn_iters < 1 or (
                self.mhc_streams > 1 and not mla_alone):
            raise ValueError(
                f"mhc_streams {self.mhc_streams} rows a position (mHC) are "
                "read by the serve programs' mla and mla_sliding layers and "
                "by ffn_block (stream_in, stream_out): every other kind's "
                "residual, and the trainer's decoder_layer, add a branch to "
                "ONE stream")
        if self.ffn_activation not in ("swiglu", "polynorm"):
            raise ValueError(
                f"unknown ffn_activation {self.ffn_activation!r}")
        if self.one_branch and (self.layer_types is None
                                or self.n_dense_layers or self.sandwich_norm):
            raise ValueError(
                "one_branch says what each entry of layer_types is (a mixer "
                "alone, or 'ffn', a feed-forward alone): it needs "
                "layer_types, and no n_dense_layers or sandwich_norm (a "
                "dense feed-forward of such a stack is an 'ffn' layer of a "
                "configuration without experts)")
        if ((self.norm_unit_offset or self.stream_fp32
             or self.head_rows != 1) and not self.stateful):
            raise ValueError(
                "norm_unit_offset, stream_fp32 and head_rows are read by "
                "the serve programs of a configuration whose layers keep a "
                "state by kind (decode.py's mixed_programs: stream_norm, "
                "embed, emit); the trainer's forward and the programs of "
                "one stack of one block do not read them")
        if self.head_rows != 1 and self.tie_embeddings:
            raise ValueError("head_rows rows a position need a head of "
                             "their own: no tie_embeddings")
        if self.tie_embeddings and self.layer_types is None:
            raise ValueError(
                "tie_embeddings is read where a configuration has "
                "layer_types (forward_with_aux's loop over layers, "
                "decode.py's mixed_programs): the pipeline, the quantized "
                "steps and the serve programs of one stack of one block "
                "take a separate lm_head")
        if self.layer_rotary is not None:
            by_kind = dict(self.layer_rotary)
            if set(by_kind) - {"sliding", "full", "mla"}:
                raise ValueError(
                    "layer_rotary is by kind of layer, 'sliding' | 'full' "
                    f"| 'mla', got {sorted(by_kind)}")
            object.__setattr__(self, "layer_rotary", tuple(sorted(
                (kind, Rotary(**how) if isinstance(how, dict) else how)
                for kind, how in by_kind.items())))
        if self.qk_norm and self.qk_norm_per_head:
            raise ValueError("qk_norm is over the whole vector, "
                             "qk_norm_per_head over each head: set one")
        if self.n_dense_layers and (self.n_experts <= 0
                                    or not self.d_ff_dense
                                    or self.n_dense_layers >= self.n_layers):
            raise ValueError(
                "n_dense_layers leads a stack of MoE layers: it needs "
                "n_experts > 0, d_ff_dense and n_dense_layers < n_layers")

    @property
    def head_dim(self) -> int:
        if self.d_head is not None:
            return self.d_head
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def mixed(self) -> bool:
        """Layers of more than one kind, or a chip's share of the
        experts: what the trainer's one scanned block over one stack
        does not describe, and the serve programs run layer by layer.
        The parameters of such a configuration are LISTS of layers (one
        dict a layer, no leading dimension) and not stacks: a program
        that names each layer takes each layer's matrices as they lie,
        where a slice of a stack is a copy (of 1.8 GB for the experts
        of the configuration that brought this)."""
        return bool(self.n_dense_layers or self.layer_types
                    or self.moe_experts_held is not None)

    def sliding(self, layer: int) -> bool:
        return (self.layer_types is not None
                and self.layer_types[layer] == "sliding")

    def kind_of(self, layer: int) -> str:
        """Layer ``layer``'s kind, one of ``LAYER_KINDS`` ("full" where
        there are no ``layer_types``)."""
        return "full" if self.layer_types is None else self.layer_types[layer]

    def n_layers_of(self, kind: str) -> int:
        return sum(self.kind_of(i) == kind for i in range(self.n_layers))

    @property
    def mamba2_heads(self) -> int:
        return self.mamba_expand * self.d_model // self.mamba2_head_dim

    @property
    def mamba2_conv_width(self) -> int:
        """Channels a mamba2 layer's convolution runs over: x | B | C."""
        return (self.mamba_expand * self.d_model
                + 2 * self.mamba2_groups * self.mamba_d_state)

    @property
    def stateful(self) -> bool:
        """Some layer keeps a state that is not cached keys and values
        alone (a kda, mamba or lightning layer's recurrent state, an mla
        layer's latent, a sparse layer's compressed keys, a conv layer's
        rows, an eva layer's window rows and summary pages, a mamba2
        layer's state, an mla_sliding layer's ring of latents), or its
        layers are one branch each: what the serve programs alone run."""
        return bool(self.layer_types) and (self.one_branch or bool(
            {"kda", "mla", "mamba", "sparse", "lightning", "conv", "eva",
             "mamba2", "mla_sliding"} & set(self.layer_types)))

    def rotary_of(self, layer: int = 0) -> Optional[Rotary]:
        """How layer ``layer`` rotates q and k, None for not at all:
        what ``layer_rotary`` says of its kind, else plainly at
        ``rope_theta``, but for the full layers of a stack with
        ``layer_types``, which then take no rotary embedding. (An mla
        layer rotates its ``mla_rope_dim`` values so; a kda, mamba or
        conv layer reads no position; a sparse layer is a full layer
        here; a lightning or eva layer rotates, in halves, plainly.)"""
        kind = self.kind_of(layer)
        if kind in ("lightning", "eva"):
            return Rotary(self.rope_theta)
        if kind in ("kda", "mamba", "sparse", "conv", "mamba2", FFN):
            kind = "full"
        if kind == "mla_sliding":
            kind = "mla"        # both latent kinds rotate alike
        by_kind = dict(self.layer_rotary or ())
        if kind in by_kind:
            return by_kind[kind]
        if self.layer_types is not None and kind == "full":
            return None
        return Rotary(self.rope_theta)

    @property
    def n_window_layers(self) -> int:
        return sum(self.sliding(i) for i in range(self.n_layers))

    @property
    def mla_signal_heads(self) -> int:
        """The mla kinds' heads that reach ``wo``: all but the noise
        heads."""
        return self.n_heads - self.mla_noise_heads

    @classmethod
    def llama3_8b(cls, **kw):
        return cls(vocab_size=128_256, d_model=4096, n_layers=32,
                   n_heads=32, n_kv_heads=8, d_ff=14_336, max_seq=8192,
                   **kw)

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                    n_kv_heads=2, d_ff=128, max_seq=128)
        base.update(kw)  # any field overridable (llama3_8b-style presets
        return cls(**base)  # hard-pin theirs; tiny is a CI scaffold)

    @property
    def moe(self) -> Optional[moe_lib.MoEConfig]:
        if self.n_experts <= 0:
            return None
        return moe_lib.MoEConfig(n_experts=self.n_experts,
                                 top_k=self.moe_top_k,
                                 capacity_factor=self.moe_capacity_factor,
                                 aux_loss_coef=self.moe_aux_loss_coef,
                                 z_loss_coef=self.moe_z_loss_coef,
                                 norm_topk_prob=self.moe_norm_topk_prob,
                                 scoring=self.moe_scoring,
                                 route_scale=self.moe_route_scale,
                                 shared_expert=self.moe_shared_expert,
                                 experts_held=self.moe_experts_held,
                                 expert_offset=self.moe_expert_offset,
                                 n_group=self.moe_n_group,
                                 topk_group=self.moe_topk_group,
                                 activation=self.moe_activation,
                                 latent=self.moe_latent,
                                 shared_d_ff=self.moe_shared_d_ff,
                                 **({"polynorm_scale": self.polynorm_scale,
                                     "polynorm_bias_clamp":
                                         self.polynorm_bias_clamp,
                                     "norm_eps": self.norm_eps}
                                    if self.moe_activation == "polynorm"
                                    else {}))


# ---------------------------------------------------------------------------
# Parameter init + sharding specs
# ---------------------------------------------------------------------------

#: The kinds of layer with no q and no k: the gains ``qk_norm_per_head``
#: gives the full layers beside them, they do not hold.
_NO_HEADS = ("mamba", "conv", "mamba2", FFN)


def _block_specs(cfg: TransformerConfig, moe: bool, kind: str = "full"
                 ) -> Dict[str, Any]:
    """The specs of one stack of blocks: MoE blocks or dense ones, with
    the attention of ``kind`` (``_init_blocks`` gives the shapes)."""
    mat, vec = P(None, "fsdp", "tp"), P(None, None)
    layers: Dict[str, Any] = {
        "attn_norm": P(None, None),    # [L, D]
        "wq": P(None, "fsdp", "tp"),   # [L, D, H*Dh]
        "wk": P(None, "fsdp", "tp"),   # [L, D, Hkv*Dh]
        "wv": P(None, "fsdp", "tp"),
        "wo": P(None, "tp", "fsdp"),   # [L, H*Dh, D]
        "mlp_norm": P(None, None),
    }
    if kind == "lightning":
        layers["o_norm"] = P(None, "tp")   # [L, H*Dh], as wo's rows
    if kind == "eva":
        layers.update(eva_mu=P(None, "tp", None),   # [L, Hkv, Dh]
                      eva_phi=P(None, "tp", None))
    if kind == "kda":
        layers.update(conv_q=P(None, None, "tp"), conv_k=P(None, None, "tp"),
                      conv_v=P(None, None, "tp"), wa=mat, a_log=vec,
                      a_bias=P(None, "tp"), wbeta=mat, wz=mat, o_norm=vec)
    if kind == "mamba":
        layers = {"attn_norm": vec, "mlp_norm": vec,
                  "w_in": mat, "conv_w": P(None, None, "tp"),
                  "conv_b": P(None, "tp"), "w_x": P(None, "tp", None),
                  "dt_norm": vec, "b_norm": vec, "c_norm": vec,
                  "w_dt": P(None, None, "tp"), "b_dt": P(None, "tp"),
                  "a_log": P(None, None, "tp"), "d_skip": P(None, "tp"),
                  "w_out": P(None, "tp", "fsdp")}
    if kind == "conv":
        layers = {"attn_norm": vec, "mlp_norm": vec, "w_in": mat,
                  "conv_w": P(None, None, "tp"),
                  "w_out": P(None, "tp", "fsdp")}
    if kind == "mamba2":
        layers = {"attn_norm": vec, "mlp_norm": vec, "w_in": mat,
                  "w_dt": mat,
                  "conv_w": P(None, None, "tp"), "conv_b": P(None, "tp"),
                  "dt_bias": P(None, "tp"), "a_log": P(None, "tp"),
                  "d_skip": P(None, "tp"), "o_norm": P(None, "tp"),
                  "w_out": P(None, "tp", "fsdp")}
    if kind == FFN:
        layers = {"mlp_norm": vec}
    if kind in MLA_KINDS:
        del layers["wk"], layers["wv"]
        layers.update(w_dkv=P(None, "fsdp", None), kv_norm=vec,
                      w_ukv=P(None, None, "tp"))
        if cfg.mla_q_rank:
            del layers["wq"]
            layers.update(w_dq=P(None, "fsdp", None), dq_norm=vec,
                          w_uq=P(None, None, "tp"))
        if cfg.mla_head_gate or cfg.mla_elementwise_gate:
            layers["wg"] = mat
        if cfg.mla_noise_heads:
            layers["w_lambda"] = mat
    if cfg.qk_norm:
        layers["q_norm"] = P(None, "tp")   # [L, H*Dh], as wq's columns
        layers["k_norm"] = P(None, "tp")   # [L, Hkv*Dh]
    if cfg.qk_norm_per_head and kind not in _NO_HEADS:
        layers["q_norm"] = P(None, None)   # [L, Dh], every head's gain
        layers["k_norm"] = P(None, None)
    if cfg.attn_gate:
        layers["wg"] = P(None, "fsdp", "tp")   # [L, D, H*Dh]
    if cfg.sandwich_norm:
        layers["post_attn_norm"] = P(None, None)
        layers["post_mlp_norm"] = P(None, None)
    if cfg.mhc_streams > 1:
        # float32, a branch: [L, n * D, n * n + 2 n], [L, n * n + 2 n], [L, 3]
        layers.update({f"mhc_{branch}": {"w": P(None, None, None),
                                         "b": vec, "alpha": vec}
                       for branch in ("attn", "mlp")})
    if cfg.one_branch and kind != FFN:
        del layers["mlp_norm"]          # a mixer alone
        return layers
    if moe:
        layers["moe"] = moe_lib.moe_param_specs(cfg=cfg.moe)
    else:
        if cfg.ffn_activation == "polynorm":
            layers.update(poly_w=vec, poly_b=P(None))
        layers.update({
            # Separate gate/up/q/k/v matmuls measure FASTER than fused
            # wide projections on v5e at d=2048-4096 (fusion costs the
            # output slices more than the larger tile buys: 42.7% vs
            # 46.3% MFU) — keep the unfused layout.
            "w_gate": P(None, "fsdp", "tp"),  # [L, D, F]
            "w_up": P(None, "fsdp", "tp"),
            "w_down": P(None, "tp", "fsdp"),  # [L, F, D]
        })
    return layers


def param_specs(cfg: TransformerConfig) -> Dict[str, Any]:
    """PartitionSpec pytree matching :func:`init_params`.

    ``tp`` shards heads / FFN hidden / vocab; ``fsdp`` shards the
    other matrix dim. Layer-stacked leaves carry a leading ``None``
    (the scan dim is never sharded).
    """
    specs = {
        # [V, D] vocab-parallel; looked up via the explicit shard_map
        # island in :func:`embed_lookup` — a global-view gather on a
        # vocab-sharded table forces GSPMD into "involuntary full
        # rematerialization" (replicate the table, then re-partition).
        "embed": P("tp", "fsdp"),
        "layers": _block_specs(cfg, cfg.moe is not None),
        "final_norm": P(None),
        "lm_head": P("fsdp", "tp"),        # [D, head_rows * V]
    }
    if cfg.tie_embeddings:
        del specs["lm_head"]
    if cfg.n_dense_layers:
        specs["dense_layers"] = _block_specs(cfg, False)
    if cfg.mixed:
        for stack, first, n in (
                ("layers", cfg.n_dense_layers,
                 cfg.n_layers - cfg.n_dense_layers),
                ("dense_layers", 0, cfg.n_dense_layers)):
            if stack in specs:
                specs[stack] = [jax.tree.map(
                    lambda spec: P(*spec[1:]),
                    _block_specs(cfg, stack == "layers"
                                 and cfg.moe is not None,
                                 cfg.kind_of(first + i)),
                    is_leaf=lambda x: isinstance(x, P)) for i in range(n)]
    return specs


def _init_blocks(cfg: TransformerConfig, k, L: int, moe: bool, F: int,
                 kind: str = "full"):
    """One stack of ``L`` blocks, MoE or dense of width ``F``, with the
    attention of ``kind``, drawing its keys from the iterator ``k`` in
    one fixed order."""
    D, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if kind == "lightning":
        Hkv = H                  # as many heads for k and v as for q
    dt = cfg.dtype

    def dense(kk, shape, fan_in):
        return (jax.random.normal(kk, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dt)

    def uniform(kk, shape, lo, hi):
        return jax.random.uniform(kk, shape, jnp.float32, lo, hi)

    if kind == "kda":
        layers = {
            "attn_norm": jnp.ones((L, D), dt),
            "wq": dense(next(k), (L, D, H * Dh), D),
            "wk": dense(next(k), (L, D, H * Dh), D),
            "wv": dense(next(k), (L, D, H * Dh), D),
            # a tap a channel: [taps, H*Dh], the last tap the newest row
            "conv_q": dense(next(k), (L, cfg.kda_conv, H * Dh), cfg.kda_conv),
            "conv_k": dense(next(k), (L, cfg.kda_conv, H * Dh), cfg.kda_conv),
            "conv_v": dense(next(k), (L, cfg.kda_conv, H * Dh), cfg.kda_conv),
            # the decay: floor * sigmoid(exp(a_log) * (h wa + a_bias)), a
            # head's rate in [1, 2) and biases that leave a channel
            # between a few and some thousands of positions of memory
            "wa": dense(next(k), (L, D, H * Dh), D),
            "a_log": jnp.log(uniform(next(k), (L, H), 1.0, 2.0)),
            "a_bias": uniform(next(k), (L, H * Dh), -6.0, -2.0),
            "wbeta": dense(next(k), (L, D, H), D),
            "wz": dense(next(k), (L, D, H * Dh), D),
            "o_norm": jnp.ones((L, Dh), dt),
            "wo": dense(next(k), (L, H * Dh, D), H * Dh),
            "mlp_norm": jnp.ones((L, D), dt),
        }
    elif kind == "mamba":
        Di, N, R = cfg.mamba_expand * D, cfg.mamba_d_state, cfg.mamba_dt_rank
        # Mamba's own initialisation of the step: softplus(b_dt) is
        # log-uniform in [1e-3, 1e-1], so that with A = -(1..N) a
        # channel's memories spread from a few positions to a thousand
        step = jnp.exp(uniform(next(k), (L, Di), jnp.log(1e-3),
                               jnp.log(1e-1)))
        layers = {
            "attn_norm": jnp.ones((L, D), dt),
            "w_in": dense(next(k), (L, D, 2 * Di), D),        # u | z
            # a tap a channel: [taps, Di], the last tap the newest row
            "conv_w": dense(next(k), (L, cfg.mamba_d_conv, Di),
                            cfg.mamba_d_conv),
            "conv_b": dense(next(k), (L, Di), cfg.mamba_d_conv),
            "w_x": dense(next(k), (L, Di, R + 2 * N), Di),   # delta | B | C
            "dt_norm": jnp.ones((L, R), dt),
            "b_norm": jnp.ones((L, N), dt),
            "c_norm": jnp.ones((L, N), dt),
            "w_dt": dense(next(k), (L, R, Di), R),
            # softplus^-1(step); float32, as a_log and d_skip are
            "b_dt": step + jnp.log(-jnp.expm1(-step)),
            # A = -exp(a_log), row n of [N, Di] the decay rate n + 1 of
            # every channel (the published [Di, N], turned so that a
            # state's channels lie along the lanes: kv_cache.KVCache)
            "a_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[:, None],
                (L, N, Di)),
            "d_skip": jnp.ones((L, Di), jnp.float32),
            "w_out": dense(next(k), (L, Di, D), Di),
            "mlp_norm": jnp.ones((L, D), dt),
        }
    elif kind == "conv":
        layers = {
            "attn_norm": jnp.ones((L, D), dt),
            "w_in": dense(next(k), (L, D, 3 * D), D),         # B | C | u
            # a tap a channel: [taps, D], the last tap the newest row
            "conv_w": dense(next(k), (L, cfg.conv_taps, D), cfg.conv_taps),
            "w_out": dense(next(k), (L, D, D), D),
            "mlp_norm": jnp.ones((L, D), dt),
        }
    elif kind == "mamba2":
        Di, Hm, W = (cfg.mamba_expand * D, cfg.mamba2_heads,
                     cfg.mamba2_conv_width)
        # Mamba-2's own initialisation: softplus(dt_bias) log-uniform in
        # [least, most], floored; A = -U(1, 16), ONE scalar a head; D = 1
        least, most, floor = cfg.mamba2_dt_range
        step = jnp.maximum(jnp.exp(uniform(
            next(k), (L, Hm), jnp.log(least), jnp.log(most))), floor)
        layers = {
            "attn_norm": jnp.ones((L, D), dt),
            # the published in_proj's columns [z | xBC | dt] as two
            # matrices: dt comes out in float32 (mamba2_rows)
            "w_in": dense(next(k), (L, D, Di + W), D),        # z | xBC
            "w_dt": dense(next(k), (L, D, Hm), D),
            # a tap a channel: [taps, W], the last tap the newest row
            "conv_w": dense(next(k), (L, cfg.mamba_d_conv, W),
                            cfg.mamba_d_conv),
            "conv_b": dense(next(k), (L, W), cfg.mamba_d_conv),
            # softplus^-1(step); float32, as a_log and d_skip are
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "a_log": jnp.log(uniform(next(k), (L, Hm), 1.0, 16.0)),
            "d_skip": jnp.ones((L, Hm), jnp.float32),
            # the gated norm's gain, over all Di channels
            "o_norm": jnp.ones((L, Di), dt),
            "w_out": dense(next(k), (L, Di, D), Di),
            "mlp_norm": jnp.ones((L, D), dt),
        }
    elif kind == FFN:
        layers = {"mlp_norm": jnp.ones((L, D), dt)}
    elif kind in MLA_KINDS:
        R, C, Q = cfg.mla_rope_dim, cfg.mla_kv_rank, cfg.mla_q_rank
        Hs = cfg.mla_signal_heads
        # a head's q: Dh without position, then R rotated; with a q
        # rank, up from a normed latent of Q values
        q = ({"w_dq": dense(next(k), (L, D, Q), D),
              "dq_norm": jnp.ones((L, Q), dt),
              "w_uq": dense(next(k), (L, Q, H * (Dh + R)), Q)} if Q
             else {"wq": dense(next(k), (L, D, H * (Dh + R)), D)})
        layers = {
            "attn_norm": jnp.ones((L, D), dt),
            **q,
            # the latent, then the R rotated values every head shares
            "w_dkv": dense(next(k), (L, D, C + R), D),
            "kv_norm": jnp.ones((L, C), dt),
            # a KV head's key without position, then its value
            "w_ukv": dense(next(k), (L, C, Hkv * 2 * Dh), C),
            **({"wg": dense(next(k), (L, D, H), D)}
               if cfg.mla_head_gate else {}),
            "wo": dense(next(k), (L, Hs * Dh, D), Hs * Dh),
            "mlp_norm": jnp.ones((L, D), dt),
        }
        if cfg.mla_elementwise_gate:
            layers["wg"] = dense(next(k), (L, D, Hs * Dh), D)
        if cfg.mla_noise_heads:
            layers["w_lambda"] = dense(next(k), (L, D, Hs), D)
    else:
        layers = {
            "attn_norm": jnp.ones((L, D), dt),
            "wq": dense(next(k), (L, D, H * Dh), D),
            "wk": dense(next(k), (L, D, Hkv * Dh), D),
            "wv": dense(next(k), (L, D, Hkv * Dh), D),
            "wo": dense(next(k), (L, H * Dh, D), H * Dh),
            "mlp_norm": jnp.ones((L, D), dt),
        }
        if kind == "lightning":
            layers["o_norm"] = jnp.ones((L, H * Dh), dt)
        if kind == "eva":
            # the pooling vectors, of the keys' own size (entries
            # N(0, 1)), so that a chunk's pooling weights are a softmax
            # of unit-variance logits and not a mean
            layers["eva_mu"] = dense(next(k), (L, Hkv, Dh), 1)
            layers["eva_phi"] = dense(next(k), (L, Hkv, Dh), 1)
    if cfg.norm_unit_offset:
        # the stored gain is g of 1 + g
        for name in {"attn_norm", "mlp_norm"} & set(layers):
            layers[name] = jnp.zeros_like(layers[name])
    if cfg.qk_norm:
        layers["q_norm"] = jnp.ones((L, H * Dh), dt)
        layers["k_norm"] = jnp.ones((L, Hkv * Dh), dt)
    if cfg.qk_norm_per_head and kind not in _NO_HEADS:
        layers["q_norm"] = jnp.ones((L, Dh), dt)
        layers["k_norm"] = jnp.ones((L, Dh), dt)
    if cfg.attn_gate:
        layers["wg"] = dense(next(k), (L, D, H * Dh), D)
    if cfg.sandwich_norm:
        layers["post_attn_norm"] = jnp.ones((L, D), dt)
        layers["post_mlp_norm"] = jnp.ones((L, D), dt)
    if cfg.mhc_streams > 1:
        n = cfg.mhc_streams
        for branch in ("attn", "mlp"):
            # the three mappings' matrices side by side, [W_pre | W_post |
            # W_res]: logits of unit variance on the normed stream, and
            # biases drawn as wide (mhc_identity_init false), so that the
            # streams do mix and 20 alternations differ from 1; float32
            layers[f"mhc_{branch}"] = {
                "w": (jax.random.normal(next(k), (L, n * D, n * n + 2 * n),
                                        jnp.float32) * (n * D) ** -0.5),
                "b": jax.random.normal(next(k), (L, n * n + 2 * n),
                                       jnp.float32),
                "alpha": jnp.ones((L, 3), jnp.float32)}
    if cfg.one_branch and kind != FFN:
        del layers["mlp_norm"]          # a mixer alone
        return layers
    if moe:
        layers["moe"] = moe_lib.init_moe_params(next(k), L, D, F, cfg.moe, dt)
    else:
        if cfg.ffn_activation == "polynorm":
            layers.update(moe_lib.init_polynorm(next(k), (L,)))
        layers.update({
            "w_gate": dense(next(k), (L, D, F), D),
            "w_up": dense(next(k), (L, D, F), D),
            "w_down": dense(next(k), (L, F, D), F),
        })
    return layers


def init_params(cfg: TransformerConfig, key: jax.Array,
                mesh: Optional[Mesh] = None) -> Dict[str, Any]:
    """Initialise the parameter pytree (optionally already sharded onto
    ``mesh`` so giant models never materialise replicated)."""
    k = iter(jax.random.split(key, 16))
    D, V = cfg.d_model, cfg.vocab_size

    def dense(kk, shape, fan_in):
        return (jax.random.normal(kk, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(cfg.dtype)

    if cfg.stateful:
        # a layer at a time, each of its own kind and from its own key
        def one(i):
            sparse = cfg.moe is not None and i >= cfg.n_dense_layers
            block = _init_blocks(
                cfg, iter(jax.random.split(jax.random.fold_in(key, 2 + i),
                                           16)), 1, sparse,
                cfg.d_ff if sparse or not cfg.n_dense_layers
                else cfg.d_ff_dense, cfg.kind_of(i))
            return jax.tree.map(lambda a: a[0], block)

        params = {
            "layers": [one(i) for i in range(cfg.n_dense_layers,
                                             cfg.n_layers)],
            "embed": dense(next(k), (V, D), D),
            "final_norm": (jnp.zeros if cfg.norm_unit_offset
                           else jnp.ones)((D,), cfg.dtype),
            "lm_head": dense(next(k), (D, cfg.head_rows * V), D),
        }
        if cfg.tie_embeddings:
            del params["lm_head"]
        if cfg.n_dense_layers:
            params["dense_layers"] = [one(i)
                                      for i in range(cfg.n_dense_layers)]
        return _on_mesh(cfg, params, mesh)
    params = {
        "layers": _init_blocks(cfg, k, cfg.n_layers - cfg.n_dense_layers,
                               cfg.moe is not None, cfg.d_ff),
        "embed": dense(next(k), (V, D), D),
        "final_norm": jnp.ones((D,), cfg.dtype),
        "lm_head": dense(next(k), (D, V), D),
    }
    if cfg.tie_embeddings:
        del params["lm_head"]
    if cfg.n_dense_layers:
        params["dense_layers"] = _init_blocks(
            cfg, iter(jax.random.split(jax.random.fold_in(key, 1), 8)),
            cfg.n_dense_layers, False, cfg.d_ff_dense)
    if cfg.mixed:
        for stack in ("layers", "dense_layers"):
            if stack in params:
                n = len(params[stack]["attn_norm"])
                params[stack] = [jax.tree.map(lambda a: a[i], params[stack])
                                 for i in range(n)]
    return _on_mesh(cfg, params, mesh)


def _on_mesh(cfg: TransformerConfig, params, mesh: Optional[Mesh]):
    if mesh is not None:
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 param_specs(cfg),
                                 is_leaf=lambda x: isinstance(x, P))
        params = jax.device_put(params, shardings)
    return params


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _rmsnorm(x, w, eps, unit_offset: bool = False, dtype=None):
    """``x / rms(x) * w`` in float32, in ``x``'s dtype (``dtype``:
    another); ``unit_offset``: times ``1 + w``, the sum in float32."""
    h = x.astype(jnp.float32)
    h = h * lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
    w = w.astype(jnp.float32)
    if unit_offset:
        w = 1.0 + w
    return (h * w).astype(dtype or x.dtype)


def stream_norm(cfg: "TransformerConfig", x, w):
    """The norm of the stream ``x`` before a branch or the head, as the
    configuration has it: the ONE place that reads ``norm_unit_offset``
    and ``stream_fp32`` (a float32 stream's norm gives ``cfg.dtype``).
    With neither it is ``_rmsnorm(x, w, cfg.norm_eps)``, operation for
    operation."""
    if not (cfg.norm_unit_offset or cfg.stream_fp32):
        return _rmsnorm(x, w, cfg.norm_eps)
    return _rmsnorm(x, w, cfg.norm_eps, cfg.norm_unit_offset,
                    cfg.dtype if cfg.stream_fp32 else None)


def _rope(x, pos, rotary: Rotary):
    """Rotary embedding. x: [B, T, H, D]; pos: global positions, [T]
    shared by every row (the trainer) or [B, T], one per row (the
    server: each sequence of a decode batch is at its own length)."""
    d = x.shape[-1]
    if rotary.factor is None:
        # as every program before YaRN computed it, to the instruction
        inv = 1.0 / (rotary.theta
                     ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    else:
        inv = jnp.asarray(rotary.frequencies(d))
    ang = pos[..., None].astype(jnp.float32) * inv             # [.., T, D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if rotary.attention_factor != 1.0:
        cos, sin = (cos * rotary.attention_factor,
                    sin * rotary.attention_factor)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    # to x's rank: the batch axis a shared table lacks, and the heads'
    at = (None,) * (x.ndim - 1 - ang.ndim) + (..., None, slice(None))
    cos, sin = cos[at], sin[at]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    y = jnp.stack([y1, y2], axis=-1).reshape(x.shape)
    return y.astype(x.dtype)


def _replicated_table_lookup(embed, tokens, dtype, mesh, codec: str):
    """The table-replication fallback of :func:`embed_lookup`, with the
    replication reshard — the table-sized all-gather the island exists
    to avoid — optionally shipped narrow. ``codec`` "none" is the exact
    pre-existing path (annotated f32/bf16 reshard); "bf16"/"fp16" cast
    the table to the wire dtype before the constraint; "int8" ships
    blockwise q+scales (``ops/quantized.py`` codec, ~4x vs f32) and
    dequantizes only the gathered rows."""
    from jax.sharding import NamedSharding as NS

    from horovod_tpu.ops.quantized import _CAST_WIRE

    if codec in _CAST_WIRE:
        t = lax.with_sharding_constraint(
            embed.astype(_CAST_WIRE[codec]), NS(mesh, P(None, None)))
        return t[tokens].astype(dtype)
    if codec == "int8":
        from horovod_tpu.ops.quantized import (
            blockwise_int8_decode, blockwise_int8_encode)
        q, s = blockwise_int8_encode(embed)
        q = lax.with_sharding_constraint(q, NS(mesh, P(None, None)))
        s = lax.with_sharding_constraint(s, NS(mesh, P(None, None)))
        rows = blockwise_int8_decode(q[tokens], s[tokens], embed.shape[-1])
        return rows.astype(dtype)
    replicated = lax.with_sharding_constraint(
        embed, NS(mesh, P(None, None)))
    return replicated.astype(dtype)[tokens]


def embed_lookup(embed, tokens, dtype, mesh: Optional[Mesh],
                 compression=None):
    """Vocab-parallel embedding lookup (Megatron recipe, TPU island).

    With the table sharded ``P("tp", "fsdp")``, each device holds a
    ``[V/tp, D/fsdp]`` tile. A global-view ``table[tokens]`` forces
    GSPMD to replicate the whole table every step ("involuntary full
    rematerialization", spmd_partitioner.cc) — at Llama-3-8B scale an
    all-gather of a ~1 GB table per step. Instead we run a shard_map
    island manual over ``{tp, fsdp}`` only (dp/sp stay under GSPMD):
    mask out-of-range tokens, gather locally, ``psum`` the partial rows
    over ``tp`` and ``all_gather`` the model dim over ``fsdp`` — all
    collectives are activation-sized, never table-sized.

    Reference analog: none — the reference (torch DDP-style) replicates
    embeddings on every rank; vocab-parallelism is the TPU-first design.

    ``compression`` (a ``hvd.Compression`` member; None = uncompressed)
    narrows the table-replication *fallback* paths below — the case
    where the whole table actually moves every step. The island path
    ignores it: its wires are activation-sized psums/gathers already in
    the model dtype, nothing table-sized to compress.
    """
    from horovod_tpu import compression as compression_lib

    codec = compression_lib.in_jit_codec(compression)
    V, D = embed.shape
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    fsdp = mesh.shape.get("fsdp", 1) if mesh is not None else 1
    if tp * fsdp == 1:
        return embed.astype(dtype)[tokens]
    if V % tp or D % fsdp:
        import warnings
        warnings.warn(
            f"embed_lookup: table [{V}, {D}] not divisible by "
            f"(tp={tp}, fsdp={fsdp}); falling back to a global-view "
            "gather, which forces GSPMD to replicate the table every "
            "step. Pad vocab_size/d_model to multiples of the mesh axes.")
        if codec != "none" and mesh is not None:
            return _replicated_table_lookup(embed, tokens, dtype, mesh,
                                            codec)
        return embed.astype(dtype)[tokens]
    v_loc = V // tp
    # XLA-CPU workaround (same as pipeline.py): shard_map-level bf16
    # psum/reduce-scatter crashes the CPU AllReducePromotion pass; keep
    # island wires f32 on CPU. TPU reduces bf16 natively.
    f32_wire = (jax.default_backend() == "cpu" and dtype == jnp.bfloat16)
    wire = jnp.float32 if f32_wire else dtype

    def island(table, toks):
        start = lax.axis_index("tp") * v_loc
        idx = toks - start
        valid = (idx >= 0) & (idx < v_loc)
        rows = table.astype(wire)[jnp.where(valid, idx, 0)]
        rows = jnp.where(valid[..., None], rows, jnp.zeros((), wire))
        rows = lax.psum(rows, "tp")
        return lax.all_gather(rows, "fsdp", axis=-1, tiled=True)

    # check_vma=False: the VMA checker cannot infer that a tiled
    # all_gather's output is replicated over the gathered axis (same
    # limitation as the ring_flash island in ring_attention.py).
    out = jax.shard_map(island, mesh=mesh,
                        in_specs=(P("tp", "fsdp"), P()), out_specs=P(),
                        axis_names={"tp", "fsdp"},
                        check_vma=False)(embed, tokens)
    return out.astype(dtype)


def _attention_island(cfg: TransformerConfig, mesh: Optional[Mesh],
                      window: Optional[int] = None):
    """attn(q, k, v) — ring/Ulysses shard_map island over ``sp`` when a
    mesh with sp>1 is given, plain attention otherwise (single
    construction point: :func:`~horovod_tpu.parallel.ring_attention.make_sp_attention`).
    ``window``: a sliding layer's, None for attention over everything."""
    if mesh is not None and "sp" not in mesh.axis_names:
        mesh = None
    return make_sp_attention(mesh, axis_name="sp", impl=cfg.sp_attention,
                             causal=True, block_q=cfg.flash_block_q,
                             block_k=cfg.flash_block_k, window=window)


def _attention_by_layer(cfg: TransformerConfig, mesh: Optional[Mesh]):
    """``attend_of(layer)``: the attention of that layer's kind, over
    the window for a sliding layer of a stack with ``layer_types`` and
    over everything otherwise (each kind's island built once)."""
    full = _attention_island(cfg, mesh)
    if not cfg.n_window_layers:
        return lambda layer=0: full
    windowed = _attention_island(cfg, mesh, cfg.attn_window)
    return lambda layer=0: windowed if cfg.sliding(layer) else full


def remat_policy_fn(cfg: TransformerConfig):
    """jax.checkpoint policy for the layer remat (None = full)."""
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if cfg.remat_policy == "dots_all":
        # Save EVERY matmul output (attention scores included):
        # backward recomputes only elementwise ops — the highest-MFU
        # remat tier when HBM allows (measured +3-4 MFU points over
        # "dots" at d=2048x8L on v5e).
        return jax.checkpoint_policies.dots_saveable
    if cfg.remat_policy == "full":
        return None
    raise ValueError(f"unknown remat_policy {cfg.remat_policy!r}")


def _constrainer(mesh: Optional[Mesh]):
    def constrain(x, *spec):
        if mesh is not None:
            return lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(*spec)))
        return x
    return constrain


def attention_inputs(cfg: TransformerConfig, lp, x, pos, layer: int = 0):
    """A decoder block up to its attention, for :func:`decoder_layer`
    and the serve programs (``serve/decode.py``): pre-norm, q/k/v
    projections, the q/k norm where configured (over the whole vector,
    or over each head), heads, and the rotary embedding at ``pos`` ([T]
    or [B, T]) as layer ``layer``'s kind takes it
    (``cfg.rotary_of(layer)``: every layer alike without
    ``layer_types``). ``x`` [B, T, D] → q [B, T, H, Dh], k and v
    [B, T, Hkv, Dh] (no GQA repeat: what the server's cache stores)."""
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    B, T = x.shape[0], x.shape[1]
    eva = cfg.kind_of(layer) == "eva"
    h = stream_norm(cfg, x, lp["attn_norm"])

    def project(w, norm, heads):
        if eva:
            # one product over the heads' own dimensions: as `h @ w`
            # reshaped afterwards the v5e's compiler TRANSPOSED wq, wk
            # and wv (32 MB each at EvaByte's widths) on every call, a
            # chunk's and a decode step's alike (compiled for the v5e,
            # PR 56: 3 copies a layer, none in this form)
            y = jnp.einsum("btd,dhk->bthk", h, lp[w].reshape(-1, heads, Dh))
        else:
            y = h @ lp[w]
            if cfg.qk_norm:
                with jax.named_scope("qk_norm"):
                    y = _rmsnorm(y, lp[norm], cfg.norm_eps)
            y = y.reshape(B, T, heads, Dh)
        if cfg.qk_norm_per_head and norm:
            with jax.named_scope("qk_norm"):
                y = _rmsnorm(y, lp[norm], cfg.norm_eps)
        return y

    q = project("wq", "q_norm", H)
    k = project("wk", "k_norm", Hkv)
    v = (project("wv", None, Hkv) if eva
         else (h @ lp["wv"]).reshape(B, T, Hkv, Dh))
    rotary = cfg.rotary_of(layer)
    if rotary is None:
        return q, k, v
    if eva:
        # in halves (float32 there), back in the projections' dtype
        return (_rope_halves(q, pos, rotary).astype(q.dtype),
                _rope_halves(k, pos, rotary).astype(k.dtype), v)
    return _rope(q, pos, rotary), _rope(k, pos, rotary), v


def attention_residual(cfg: TransformerConfig, lp, x, o):
    """A decoder block after its attention ``o`` [B, T, H * Dh]: the
    output gate and the norm on the branch where configured, the output
    projection and the residual."""
    if cfg.attn_gate:
        with jax.named_scope("attn_gate"):
            # the layer's normed input once more: one value with
            # attention_inputs' to the compiler
            u = _rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
            o = o * jax.nn.sigmoid(
                (u @ lp["wg"]).astype(jnp.float32)).astype(o.dtype)
    y = (o @ lp["wo"]).astype(cfg.dtype)
    if cfg.sandwich_norm:
        y = _rmsnorm(y, lp["post_attn_norm"], cfg.norm_eps)
    return x + _branch(cfg, y)


def _branch(cfg: TransformerConfig, y):
    """A residual branch's output as the stream takes it: times
    ``residual_multiplier`` where the configuration has one."""
    if cfg.residual_multiplier is None:
        return y
    return y * jnp.asarray(cfg.residual_multiplier, y.dtype)


# -- the two kinds of layer whose state is not cached keys (ISSUE 38) --
# Their projections, gates and norms, for the serve programs
# (``serve/decode.py``), which own the recurrence and the attention over
# the latent pool as they own the attention over pages.

def kda_rows(cfg: TransformerConfig, lp, x):
    """A kda layer up to its convolution: the normed input ``h``
    [B, T, D] and the rows ``h (Wq | Wk | Wv)`` [B, T, 3 * H * Dh] that
    the convolution runs over (the newest ``kda_conv - 1`` of them are
    what a sequence carries from call to call)."""
    h = _rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
    return h, jnp.concatenate([h @ lp["wq"], h @ lp["wk"], h @ lp["wv"]], -1)


def kda_conv(cfg: TransformerConfig, lp, rows, before):
    """The causal depthwise convolution and SiLU over ``rows``
    [B, T, 3 * H * Dh] preceded by the sequence's ``before``
    [B, kda_conv - 1, 3 * H * Dh] (zeros at a sequence's start), and the
    L2 norm of q and k over each head: q [B, T, H, Dh] scaled by
    ``Dh ** -0.5``, k, v, float32."""
    B, T = rows.shape[:2]
    H, Dh = cfg.n_heads, cfg.head_dim
    taps = jnp.concatenate([lp["conv_q"], lp["conv_k"], lp["conv_v"]], -1)
    full = jnp.concatenate([before.astype(rows.dtype), rows], 1)
    y = sum(full[:, j:j + T].astype(jnp.float32)
            * taps[j].astype(jnp.float32) for j in range(cfg.kda_conv))
    q, k, v = (a.reshape(B, T, H, Dh)
               for a in jnp.split(jax.nn.silu(y), 3, axis=-1))

    def unit(a):
        return a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    return unit(q) * Dh ** -0.5, unit(k), v


def kda_gates(cfg: TransformerConfig, lp, h):
    """The log-decay a channel ``g = floor * sigmoid(exp(a_log) * (h Wa
    + a_bias))`` [B, T, H, Dh] (the floor, -5 as published, bounds it
    below: 16 positions decay by at most e^-80, which float32 holds)
    and the write strength ``beta = sigmoid(h Wbeta)`` [B, T, H]."""
    B, T = h.shape[:2]
    H, Dh = cfg.n_heads, cfg.head_dim
    # float32 out of the matrix unit: a position's log-decay adds up
    # over a channel's whole memory, and rounded to bf16 before the
    # sigmoid it is 1 % off, which e^(sum g) turns into as much as a
    # fifth of the state (chip, PR 38)
    def f32(w):
        return jnp.einsum("btd,df->btf", h, lp[w],
                          preferred_element_type=jnp.float32)

    a = (f32("wa") + lp["a_bias"]).reshape(B, T, H, Dh)
    g = cfg.kda_decay_floor * jax.nn.sigmoid(
        jnp.exp(lp["a_log"])[:, None] * a)
    return g, jax.nn.sigmoid(f32("wbeta"))


def kda_residual(cfg: TransformerConfig, lp, x, h, o):
    """A kda layer after its recurrence ``o`` [B, T, H, Dh] float32:
    RMSNorm over each head, the gate ``sigmoid(h Wz)``, the output
    projection and the residual."""
    B, T = x.shape[:2]
    o = _rmsnorm(o, lp["o_norm"], cfg.norm_eps).reshape(B, T, -1)
    o = (o * jax.nn.sigmoid((h @ lp["wz"]).astype(jnp.float32))
         ).astype(cfg.dtype)
    return x + (o @ lp["wo"]).astype(cfg.dtype)


def head_weights(cfg: TransformerConfig, params):
    """``[D, V]``: the head (``[D, head_rows * V]``, a row of
    predictions after another, where the configuration has several), or
    with ``tie_embeddings`` the embedding's transpose."""
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


# -- the state-space kind (ISSUE 47): projections, convolution, gates --

def mamba_rows(cfg: TransformerConfig, lp, x):
    """A mamba layer up to its convolution: ``[u | z] = RMSNorm(x)
    W_in``, each [B, T, Di] (``Di = mamba_expand * d_model``). ``u`` is
    what the convolution runs over (the newest ``mamba_d_conv - 1`` rows
    of it are what a sequence carries from call to call); ``z`` gates
    the output."""
    h = _rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
    return jnp.split(h @ lp["w_in"], 2, axis=-1)


def causal_taps(u, before, taps, bias=None):
    """``bias + sum_j w_j u_{t - (n - 1) + j}``, float32: the causal
    depthwise convolution of ``taps`` [n, C] (a tap a channel, the last
    the newest row) over ``u`` [B, T, C] preceded by the sequence's
    ``before`` [B, n - 1, C] (zeros at a sequence's start). A mamba
    layer's (with its bias) and a conv layer's (without) alike."""
    T = u.shape[1]
    full = jnp.concatenate([before.astype(u.dtype), u], 1)
    # (here, so that a mamba layer's program lowers as it did before the
    # two kinds shared this)
    b = None if bias is None else bias.astype(jnp.float32)
    y = sum(full[:, j:j + T].astype(jnp.float32)
            * taps[j].astype(jnp.float32) for j in range(taps.shape[0]))
    return y if b is None else b + y


def mamba_conv(cfg: TransformerConfig, lp, u, before):
    """``SiLU(b + sum_j w_j u_{t - (taps - 1) + j})``: the causal
    depthwise convolution over ``u`` [B, T, Di] preceded by the
    sequence's ``before`` [B, taps - 1, Di] (zeros at a sequence's
    start), float32 sums, in ``u``'s dtype."""
    y = causal_taps(u, before, lp["conv_w"], lp["conv_b"])
    return jax.nn.silu(y).astype(u.dtype)


def mamba_gates(cfg: TransformerConfig, lp, u):
    """What the recurrence reads of the convolved ``u`` [B, T, Di]:
    ``[delta | B | C] = u W_x``, each RMS-normed with its own gain; the
    step ``Delta = softplus(delta W_dt + b_dt)`` [B, T, Di], ``B`` and
    ``C`` [B, T, N], all float32 (a position's ``Delta A`` adds up over
    a channel's whole memory, as a kda layer's log-decay does)."""
    R, N = cfg.mamba_dt_rank, cfg.mamba_d_state
    dbc = u @ lp["w_x"]
    delta = _rmsnorm(dbc[..., :R], lp["dt_norm"], cfg.norm_eps)
    b = _rmsnorm(dbc[..., R:R + N], lp["b_norm"], cfg.norm_eps)
    c = _rmsnorm(dbc[..., R + N:], lp["c_norm"], cfg.norm_eps)
    step = jax.nn.softplus(
        jnp.einsum("btr,rd->btd", delta, lp["w_dt"],
                   preferred_element_type=jnp.float32) + lp["b_dt"])
    return step, b.astype(jnp.float32), c.astype(jnp.float32)


def mamba_residual(cfg: TransformerConfig, lp, x, y, z):
    """A mamba layer after its recurrence ``y`` [B, T, Di] float32: the
    gate ``SiLU(z)``, the output projection and the residual."""
    o = (y * jax.nn.silu(z.astype(jnp.float32))).astype(cfg.dtype)
    return x + (o @ lp["w_out"]).astype(cfg.dtype)


# -- the gated short convolution (ISSUE 54): projections, gates, taps --

def conv_inputs(cfg: TransformerConfig, lp, x):
    """A conv layer up to its gates: ``[B | C | u] = RMSNorm(x) W_in``,
    each [B, T, D]."""
    h = _rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
    return jnp.split(h @ lp["w_in"], 3, axis=-1)


def conv_gated(cfg: TransformerConfig, lp, b, c, u, before):
    """``(z, C * conv(z))``: ``z = B * u`` [B, T, D], what the
    convolution runs over (the newest ``conv_taps - 1`` rows of it are
    ALL a sequence carries from call to call), and :func:`causal_taps`
    of it after the sequence's ``before`` [B, conv_taps - 1, D], with
    neither bias nor activation, gated by ``C``; both in ``u``'s
    dtype."""
    z = b * u
    y = causal_taps(z, before, lp["conv_w"]).astype(u.dtype)
    return z, c * y


def conv_residual(cfg: TransformerConfig, lp, x, g):
    """A conv layer after its gated convolution ``g`` [B, T, D]: the
    output projection and the residual."""
    return x + (g @ lp["w_out"]).astype(cfg.dtype)


# -- Mamba-2's SSD layer (ISSUE 60): projections, convolution, gated norm --

def mamba2_rows(cfg: TransformerConfig, lp, x):
    """A mamba2 layer up to its convolution: ``[z | xBC] = RMSNorm(x)
    W_in`` as ``Di | Di + 2 G N`` and ``dt = RMSNorm(x) W_dt`` [B, T, Hm]
    in float32 out of the matrix unit (a position's ``Delta A`` adds up
    over a head's whole memory, as a kda layer's log-decay does).
    ``xBC`` is what the convolution runs over (the newest ``mamba_d_conv
    - 1`` rows of it are what a sequence carries from call to call);
    ``z`` gates the output."""
    Di = cfg.mamba_expand * cfg.d_model
    h = _rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
    zx = h @ lp["w_in"]
    dt = jnp.einsum("btd,dh->bth", h, lp["w_dt"],
                    preferred_element_type=jnp.float32)
    return zx[..., :Di], zx[..., Di:], dt


def mamba2_inputs(cfg: TransformerConfig, lp, xbc, dt, before):
    """What the recurrence reads: ``SiLU(conv(xBC))`` (:func:`causal_taps`
    with the bias, after the sequence's ``before`` [B, taps - 1, W])
    split ``x`` [B, T, Hm, P], ``B`` and ``C`` [B, T, G, N], and the step
    ``Delta = softplus(dt + dt_bias)`` [B, T, Hm], all float32."""
    B, T = xbc.shape[:2]
    Di, N, G = (cfg.mamba_expand * cfg.d_model, cfg.mamba_d_state,
                cfg.mamba2_groups)
    y = jax.nn.silu(causal_taps(xbc, before, lp["conv_w"], lp["conv_b"]))
    return (y[..., :Di].reshape(B, T, cfg.mamba2_heads, cfg.mamba2_head_dim),
            y[..., Di:Di + G * N].reshape(B, T, G, N),
            y[..., Di + G * N:].reshape(B, T, G, N),
            jax.nn.softplus(dt + lp["dt_bias"]))


def mamba2_residual(cfg: TransformerConfig, lp, x, y, z):
    """A mamba2 layer after its recurrence ``y`` [B, T, Hm, P] float32
    (``D x`` added): the gate ``SiLU(z)``, THEN the RMSNorm whose
    statistics are over each of the ``mamba2_groups`` groups of channels,
    its gain over all of them, the output projection and the residual."""
    B, T = x.shape[:2]
    G = cfg.mamba2_groups
    with jax.named_scope("mamba2_norm"):
        g = (y.reshape(B, T, G, -1)
             * jax.nn.silu(z.astype(jnp.float32)).reshape(B, T, G, -1))
        g = g * lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + cfg.norm_eps)
        o = (g.reshape(B, T, -1) * lp["o_norm"].astype(jnp.float32)
             ).astype(cfg.dtype)
    return x + (o @ lp["w_out"]).astype(cfg.dtype)


# -- the linear-attention kind (ISSUE 50): projections, decays, gate --

def _rope_halves(x, pos, rotary: Rotary):
    """Rotary embedding over the pairs ``(i, i + d / 2)`` (the rotated
    halves of the lightning layers' family; :func:`_rope` pairs
    ``(2i, 2i + 1)``). x [B, T, H, d], pos [T] or [B, T]; float32."""
    d = x.shape[-1]
    ang = (pos[..., None].astype(jnp.float32)
           * jnp.asarray(rotary.frequencies(d)))[..., None, :]
    if ang.ndim < x.ndim:
        ang = ang[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x = x.astype(jnp.float32)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def lightning_decay(cfg: TransformerConfig):
    """The heads' log-decays ``ln lambda_h = -2^(-8 (h + 1) / heads)``
    [heads], float32: Lightning Attention's fixed slopes, the same in
    every layer."""
    heads = cfg.n_heads
    return -(2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32)
                     / heads))


def lightning_inputs(cfg: TransformerConfig, lp, x, pos, layer: int):
    """A lightning layer up to its recurrence: the normed input ``h``
    and q, k, v [B, T, heads, Dh] float32: q and k normed a head where
    ``qk_norm_per_head`` and rotated at ``pos`` as ``rotary_of(layer)``
    says, q times ``Dh ** -0.5``."""
    B, T = x.shape[:2]
    heads, Dh = cfg.n_heads, cfg.head_dim
    h = _rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
    rotary = cfg.rotary_of(layer)

    def project(w, norm=None):
        y = (h @ lp[w]).reshape(B, T, heads, Dh)
        if norm is None:                       # v: neither normed nor rotated
            return y.astype(jnp.float32)
        if cfg.qk_norm_per_head:
            with jax.named_scope("qk_norm"):
                y = _rmsnorm(y, lp[norm], cfg.norm_eps)
        return _rope_halves(y, pos, rotary)

    return (h, project("wq", "q_norm") * Dh ** -0.5, project("wk", "k_norm"),
            project("wv"))


def lightning_residual(cfg: TransformerConfig, lp, x, h, o):
    """A lightning layer after its recurrence ``o`` [B, T, heads, Dh]
    float32: RMSNorm over all heads' outputs together, the gate
    ``sigmoid(h Wg)`` where ``attn_gate``, the output projection and the
    residual."""
    B, T = x.shape[:2]
    o = _rmsnorm(o.reshape(B, T, -1), lp["o_norm"], cfg.norm_eps)
    if cfg.attn_gate:
        o = o * jax.nn.sigmoid((h @ lp["wg"]).astype(jnp.float32))
    return x + _branch(cfg, (o.astype(cfg.dtype) @ lp["wo"]
                             ).astype(cfg.dtype))


def mla_rotary(cfg: TransformerConfig) -> Rotary:
    """How the mla layers rotate (``rotary_of`` of the first of them:
    ``layer_rotary`` is by kind)."""
    return cfg.rotary_of(next(i for i, kind in enumerate(cfg.layer_types)
                              if kind in MLA_KINDS))


def mla_scale(cfg: TransformerConfig) -> float:
    """The mla layers' softmax scale: ``(Dh + R)^-1/2``, times the
    rotary's ``softmax_mscale`` (YaRN's ``m^2``, 1 without)."""
    return ((cfg.head_dim + cfg.mla_rope_dim) ** -0.5
            * mla_rotary(cfg).softmax_mscale)


def mla_inputs(cfg: TransformerConfig, lp, x, pos):
    """An mla layer up to its attention: the normed input ``h``, q
    without position [B, T, H, Dh] and rotated [B, T, H, R], and what
    the cache holds of a position, ``[RMSNorm(c) | rotated r]``
    [B, T, C + R]. Rotation as :func:`mla_rotary` says, over
    interleaved pairs; q straight out of ``wq`` or, with a q rank,
    ``RMSNorm(h W_dq) W_uq``."""
    B, T = x.shape[:2]
    H, Dh, R, C = cfg.n_heads, cfg.head_dim, cfg.mla_rope_dim, cfg.mla_kv_rank
    rotary = mla_rotary(cfg)
    h = _rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
    with jax.named_scope("mla_q"):
        if cfg.mla_q_rank:
            q = _rmsnorm(h @ lp["w_dq"], lp["dq_norm"],
                         cfg.norm_eps) @ lp["w_uq"]
        else:
            q = h @ lp["wq"]
        q = q.reshape(B, T, H, Dh + R)
        qr = _rope(q[..., Dh:], pos, rotary)
    with jax.named_scope("mla_kv_down"):
        cr = h @ lp["w_dkv"]
        c = _rmsnorm(cr[..., :C], lp["kv_norm"], cfg.norm_eps)
        r = _rope(cr[..., None, C:], pos, rotary)[:, :, 0]
    return h, q[..., :Dh], qr, jnp.concatenate([c, r], -1)


def mla_up(cfg: TransformerConfig, lp):
    """``(W_uk, W_uv)`` [C, Hkv, Dh] each, out of ``w_ukv``: one pair a
    KV head (``n_kv_heads``; as many as query heads unless the heads
    are grouped), which serves the ``n_heads / n_kv_heads`` query heads
    of its group."""
    w = lp["w_ukv"].reshape(cfg.mla_kv_rank, cfg.n_kv_heads, 2,
                            cfg.head_dim)
    return w[:, :, 0], w[:, :, 1]


def gdla_diff(cfg: TransformerConfig, lp, h, o):
    """Differential heads (``mla_noise_heads``): ``o`` [B, T, H, X], a
    head's attention result in any basis ``X`` (expanded values, or the
    latent before ``W_uv``: the subtraction is linear), to the signal
    heads' ``A_s - sigmoid(h W_lambda)_s A_noise`` [B, T, Hs, X], the
    noise head the last of the signal head's KV group; float32 inside.
    Without noise heads ``o`` as it came."""
    if not cfg.mla_noise_heads:
        return o
    with jax.named_scope("gdla_diff"):
        B, T, H, X = o.shape
        G = cfg.n_kv_heads
        lam = jax.nn.sigmoid((h @ lp["w_lambda"]).astype(jnp.float32))
        o = o.astype(jnp.float32).reshape(B, T, G, H // G, X)
        o = (o[:, :, :, :-1]
             - lam.reshape(B, T, G, H // G - 1, 1) * o[:, :, :, -1:])
        return o.reshape(B, T, H - G, X).astype(cfg.dtype)


def mla_residual(cfg: TransformerConfig, lp, x, h, o, mix=None):
    """An mla layer after its attention ``o`` [B, T, Hs, Dh] (the
    signal heads: all of them without noise heads): one sigmoid gate a
    head, or one a value (``mla_elementwise_gate``), where the
    configuration has it, the output projection and the residual
    (``mix``: :func:`stream_in`'s, for :func:`stream_out`)."""
    B, T = o.shape[:2]
    if cfg.mla_head_gate:
        gate = jax.nn.sigmoid((h @ lp["wg"]).astype(jnp.float32))
        o = (o.astype(jnp.float32) * gate[..., None]).astype(cfg.dtype)
    o = o.reshape(B, T, -1)
    if cfg.mla_elementwise_gate:
        with jax.named_scope("attn_gate"):
            o = (o.astype(jnp.float32) * jax.nn.sigmoid(
                (h @ lp["wg"]).astype(jnp.float32))).astype(cfg.dtype)
    return stream_out(cfg, x, (o @ lp["wo"]).astype(cfg.dtype), mix)


def sinkhorn_knopp(logits, iters: int):
    """``iters`` alternations of row and column normalisation of
    ``exp(logits)`` [.., n, n], float32: towards a doubly stochastic
    matrix (rows first, so the columns sum to 1 exactly and the rows
    nearly)."""
    m = jnp.exp(logits - logits.max((-2, -1), keepdims=True))
    for _ in range(iters):
        m = m / m.sum(-1, keepdims=True)
        m = m / m.sum(-2, keepdims=True)
    return m


def stream_in(cfg: TransformerConfig, lp, branch: str, x):
    """What a branch (``"attn"`` or ``"mlp"``) reads of the stream
    ``x``, and what :func:`stream_out` needs to put its result back:
    ``(u, mix)``. One stream: ``(x, None)``, nothing computed. mHC
    (``mhc_streams`` n > 1, ``x`` [B, T, n, D]): the three mappings from
    the float32 RMS-normed ``vec X`` (no gain) through
    ``lp["mhc_<branch>"]``, ``u = H_pre X`` [B, T, D] and ``mix =
    (H_post [B, T, n], H_res [B, T, n, n])``."""
    if cfg.mhc_streams == 1:
        return x, None
    with jax.named_scope("mhc_mix"):
        n, p = cfg.mhc_streams, lp[f"mhc_{branch}"]
        B, T = x.shape[:2]
        flat = x.reshape(B, T, -1).astype(jnp.float32)
        flat = flat * lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                                + cfg.norm_eps)
        z = jnp.einsum("btk,km->btm", flat, p["w"],
                       precision=lax.Precision.HIGHEST)
        a = p["alpha"]
        pre = jax.nn.sigmoid(a[0] * z[..., :n] + p["b"][:n])
        post = 2.0 * jax.nn.sigmoid(a[1] * z[..., n:2 * n] + p["b"][n:2 * n])
        res = sinkhorn_knopp(
            (a[2] * z[..., 2 * n:] + p["b"][2 * n:]).reshape(B, T, n, n),
            cfg.mhc_sinkhorn_iters)
        u = jnp.einsum("btn,btnd->btd", pre, x.astype(jnp.float32))
        return u.astype(cfg.dtype), (post, res)


def stream_out(cfg: TransformerConfig, x, y, mix):
    """The stream after a branch's result ``y``: ``x + y`` on one
    stream (``mix`` None), ``H_res X + H_post^T y`` on mHC's, float32
    inside."""
    if mix is None:
        return x + y
    with jax.named_scope("mhc_mix"):
        post, res = mix
        out = (jnp.einsum("btmn,btnd->btmd", res, x.astype(jnp.float32))
               + post[..., None] * y.astype(jnp.float32)[:, :, None])
        return out.astype(x.dtype)


def ffn_block(cfg: TransformerConfig, lp, x, moe_fn=None):
    """A decoder block after its attention: what the branch reads of
    the stream (:func:`stream_in`), pre-norm, the dense SwiGLU (or
    PolyNorm: ``ffn_activation``)
    or ``moe_fn(h, lp['moe']) -> (y, aux)`` (whichever the block's
    parameters hold), the norm on the branch where configured, the
    residual (:func:`stream_out`). Returns (x, aux), aux 0 for the dense
    FFN.
    ``moe_fn=None`` is the meshless :func:`moe_lib.make_moe_ffn`: the
    plain GSPMD :func:`moe_lib.moe_ffn`, or the dropless dispatch on
    the caller's own rows for a configuration without a capacity."""
    u, mix = stream_in(cfg, lp, "mlp", x)
    h = stream_norm(cfg, u, lp["mlp_norm"])
    if "moe" in lp:
        if moe_fn is None:
            moe_fn = moe_lib.make_moe_ffn(cfg.moe, None)
        y, aux = moe_fn(h, lp["moe"])
        y = y.astype(cfg.dtype)
    else:
        g = (h @ lp["w_gate"]).astype(jnp.float32)
        if cfg.ffn_activation == "polynorm":
            g = moe_lib.polynorm(g, lp["poly_w"], lp["poly_b"],
                                 cfg.polynorm_scale, cfg.polynorm_bias_clamp,
                                 cfg.norm_eps)
        else:
            g = jax.nn.silu(g)
        u = (h @ lp["w_up"]).astype(jnp.float32)
        y = ((g * u).astype(cfg.dtype) @ lp["w_down"]).astype(cfg.dtype)
        aux = jnp.zeros((), jnp.float32)
    if cfg.sandwich_norm:
        y = _rmsnorm(y, lp["post_mlp_norm"], cfg.norm_eps)
    return stream_out(cfg, x, _branch(cfg, y), mix), aux


def _scoped(name: str, attend):
    """``attend`` under the scope ``name``, its backward with it."""
    def scoped(q, k, v):
        with jax.named_scope(name):
            return attend(q, k, v)
    scoped.handles_gqa = getattr(attend, "handles_gqa", False)
    return scoped


def decoder_layer(cfg: TransformerConfig, attend, constrain, x, lp,
                  pos_offset=0, moe_fn=None, layer: int = 0):
    """One pre-norm decoder block (attention + FFN/MoE) on ``x``
    [B, T, D]; ``lp`` is this layer's param dict (no leading L dim).
    Returns (x, aux_loss) — aux is 0 for dense FFN, the load-balancing
    term for MoE. Module-level so both the layer scan and the pipeline
    stage function build on it; the serve programs run its two halves,
    :func:`attention_inputs` and :func:`ffn_block`, around their own
    attention.

    ``moe_fn`` overrides the MoE FFN call (see :func:`ffn_block`):
    :func:`forward_with_aux` passes the
    :func:`moe_lib.make_moe_ffn`-selected dispatch plane; ``None``
    (pipeline/island callers, which run inside their own manual
    regions) keeps the meshless one.

    ``pos_offset`` shifts the rotary positions: callers running this
    layer INSIDE a manual island on a sequence SHARD (pp+sp) pass
    ``axis_index("sp") * local_T`` so every shard embeds its global
    positions; the flat path's T is already global and keeps 0.

    ``layer`` is the block's place in a stack of several kinds
    (``cfg.layer_types``): its kind sets the rotary embedding and the
    scope around ``attend``, ``attn_window`` or ``attn_full``, the names
    the serve programs write; the caller passes that kind's ``attend``."""
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    B, T = x.shape[0], x.shape[1]
    pos = jnp.arange(T) + pos_offset
    if cfg.layer_types is not None:
        attend = _scoped("attn_window" if cfg.sliding(layer)
                         else "attn_full", attend)

    with jax.named_scope("attn"):
        q, kk, vv = attention_inputs(cfg, lp, x, pos, layer)
        if Hkv != H and not getattr(attend, "handles_gqa", False):
            # GQA: tile kv heads up to H for impls that need square
            # heads (flash reads grouped K/V natively and skips this
            # copy).
            rep = H // Hkv
            kk = jnp.repeat(kk, rep, axis=2)
            vv = jnp.repeat(vv, rep, axis=2)
        o = attend(q, kk, vv).reshape(B, T, H * cfg.head_dim)
        x = attention_residual(cfg, lp, x, o)
        x = constrain(x, ("dp", "fsdp"), "sp", None)

    with jax.named_scope("mlp"):
        x, aux = ffn_block(cfg, lp, x, moe_fn)
        x = constrain(x, ("dp", "fsdp"), "sp", None)
    return x, aux


def _refuse_mixed(cfg: TransformerConfig, what: str) -> None:
    """An entry point that takes one stack of one block (the pipeline's
    stage scan, the quantized steps' islands) refuses, by name, a
    configuration whose layers are a list of several kinds or that
    holds a chip's share of the experts (``cfg.mixed``): ROADMAP C5b."""
    _refuse_stateful(cfg, what)
    if cfg.mixed:
        raise NotImplementedError(
            f"{what} does not run a configuration with layer_types, "
            "n_dense_layers or moe_experts_held: it takes one stack of one "
            "block, and such a configuration's layers are a list (ROADMAP "
            "C5b). make_train_step without compression= trains it on a "
            "dp mesh.")


def _refuse_stateful(cfg: TransformerConfig, what: str) -> None:
    """The trainer's entry points refuse kda, mla, mla_sliding, mamba,
    sparse, lightning, conv, eva and mamba2 layers, and a stack of
    one-branch layers, by name: their forward exists in the serve
    programs alone."""
    if cfg.stateful:
        raise NotImplementedError(
            f"{what} does not run kda, mla or mamba layers, nor sparse or "
            "lightning layers, nor conv layers, nor eva layers, nor mamba2 "
            "layers or layers of one branch (one_branch): "
            "models/transformer.py's decoder_layer is a mixer AND a "
            "feed-forward and has no "
            "backward through the chunked delta-rule scan, the selective "
            "scan, the decayed linear scan or the SSD chunks of "
            "serve/decode.py, no latent "
            "attention (mla, mla_sliding), no selection of key blocks and no attention over "
            "a window beside chunk summaries (ROADMAP B14, B8, B18). "
            "The configuration is served through ServeEngine.")


def _refuse_mixed_off_dp(cfg: TransformerConfig, what: str, mesh) -> None:
    """:func:`forward_with_aux`, :func:`lm_loss`, :func:`make_train_step`
    and :func:`moe_routing_report` run a ``cfg.mixed`` configuration as
    a loop over its layers, over ``dp`` alone: ``tp``, ``sp``, ``ep``,
    ``pp`` and ``fsdp`` over the lists are not shown to shard, and are
    refused by name (ROADMAP C5b)."""
    _refuse_stateful(cfg, what)
    if not cfg.mixed or mesh is None:
        return
    sharded = [f"{axis}={size}" for axis, size in dict(mesh.shape).items()
               if axis != "dp" and size > 1]
    if sharded:
        raise NotImplementedError(
            f"{what} runs a configuration with layer_types, n_dense_layers "
            f"or moe_experts_held over dp alone, not over {sharded}: the "
            "window has no ring or Ulysses island (sp), the held experts "
            "no exchange (ep), and the lists of layers are not shown to "
            "shard over tp, fsdp or pp (ROADMAP C5b)")


def _stack_of(params):
    """A mixed configuration's layers from the first down, leading
    dense ones and then the rest: the server's two lists, as one."""
    return list(params.get("dense_layers", ())) + list(params["layers"])


def forward_with_aux(params, tokens, cfg: TransformerConfig,
                     mesh: Optional[Mesh] = None):
    """tokens ``[B, T]`` int32 → (logits ``[B, T, V]``, aux_loss).

    With a mesh: activations constrained to ``P(('dp','fsdp'), 'sp')``
    on [B, T] dims; attention heads tp-sharded by GSPMD propagation from
    the weight specs.

    One block scanned over one stack of parameters; a configuration of
    several kinds of layer (``cfg.mixed``) is a loop over its lists
    instead, each layer checkpointed by itself, with its kind's
    attention and rotary embedding.
    """
    _refuse_mixed_off_dp(cfg, "forward_with_aux", mesh)
    constrain = _constrainer(mesh)
    attend_of = _attention_by_layer(cfg, mesh)
    moe_fn = (moe_lib.make_moe_ffn(cfg.moe, mesh,
                                   dispatch=cfg.moe_dispatch,
                                   codec=cfg.moe_compression)
              if cfg.moe is not None else None)

    with jax.named_scope("embed"):
        x = embed_lookup(params["embed"], tokens, cfg.dtype, mesh)
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.dtype)
        if cfg.embed_multiplier is not None:
            x = x * jnp.asarray(cfg.embed_multiplier, cfg.dtype)
        x = constrain(x, ("dp", "fsdp"), "sp", None)

    def layer(x, lp, i=0):
        return decoder_layer(cfg, attend_of(i), constrain, x, lp,
                             moe_fn=moe_fn, layer=i)

    if cfg.mixed:
        aux = jnp.zeros((), jnp.float32)
        for i, lp in enumerate(_stack_of(params)):
            one = functools.partial(layer, i=i)
            if cfg.remat:
                # no scan stands between this layer's recomputation and
                # its forward: CSE would merge them and keep what remat
                # gives up
                one = jax.checkpoint(one, policy=remat_policy_fn(cfg),
                                     prevent_cse=True)
            x, a = one(x, lp)
            aux = aux + a
    else:
        if cfg.remat:
            layer = jax.checkpoint(layer, policy=remat_policy_fn(cfg),
                                   prevent_cse=cfg.remat_prevent_cse)
        x, auxes = lax.scan(layer, x, params["layers"],
                            unroll=cfg.scan_unroll)
    with jax.named_scope("head"):
        x = _rmsnorm(x, params["final_norm"], cfg.norm_eps)
        if cfg.logit_divisor is not None:
            x = x / jnp.asarray(cfg.logit_divisor, x.dtype)
        logits = x @ head_weights(cfg, params)
        logits = constrain(logits, ("dp", "fsdp"), "sp", "tp")
    return logits, aux if cfg.mixed else auxes.sum()


def forward(params, tokens, cfg: TransformerConfig,
            mesh: Optional[Mesh] = None):
    """tokens ``[B, T]`` int32 → logits ``[B, T, V]`` (cfg.dtype)."""
    return forward_with_aux(params, tokens, cfg, mesh)[0]


def moe_routing_report(params, tokens, cfg: TransformerConfig
                       ) -> Dict[str, float]:
    """:func:`moe_lib.moe_routing_stats`' keys for every MoE layer of
    the model on ``tokens`` [B, T]: one forward pass (no mesh) in which
    each layer also counts the claims on its experts and those past
    capacity (with a chip's share of the experts: the claims on the
    held ones, and those its dispatch would not run). The overflow is
    summed over layers; the load ratio is the largest layer's. A
    configuration that holds a share also reports
    ``moe_local_pair_share``: the pairs on held experts over all
    ``B·T·K`` pairs of a layer, the share of the experts held if the
    router is even; and, where a call of ``B·T·K`` pairs is large
    enough for the held dispatch to compact
    (``moe_lib.held_row_bound``), ``moe_compact_calls_share`` and
    ``moe_held_pairs_over_bound_max``
    (``moe_lib.compaction_summary``). Host-callable telemetry, outside
    the train step: it runs a program of its own."""
    moe_fn = moe_lib.make_moe_ffn(cfg.moe, None)

    def counting(h, lp):
        y, _aux = moe_fn(h, lp)
        return y, moe_lib.routing_counts(h, lp["router"], cfg.moe,
                                         lp.get("router_bias"))

    @jax.jit
    def run(params, tokens):
        x = embed_lookup(params["embed"], tokens, cfg.dtype, None)
        attend_of = _attention_by_layer(cfg, None)
        if not cfg.mixed:
            return lax.scan(
                lambda x, lp: decoder_layer(cfg, attend_of(),
                                            _constrainer(None), x, lp,
                                            moe_fn=counting),
                x, params["layers"])[1]
        counted = []
        for i, lp in enumerate(_stack_of(params)):
            x, counts = decoder_layer(cfg, attend_of(i), _constrainer(None),
                                      x, lp, moe_fn=counting, layer=i)
            if "moe" in lp:
                counted.append(counts)
        return tuple(jnp.stack(c) for c in zip(*counted))

    counts, overflow = run(params, tokens)
    report = moe_lib.routing_summary(counts, overflow)
    if cfg.moe_experts_held is not None:
        pairs = tokens.shape[0] * tokens.shape[1] * cfg.moe_top_k
        report["moe_local_pair_share"] = float(counts.sum(-1).mean()) / pairs
        report.update(moe_lib.compaction_summary(counts, pairs, cfg.moe))
    return report


def lm_loss(params, batch, cfg: TransformerConfig,
            mesh: Optional[Mesh] = None):
    """Next-token cross-entropy (f32 log-softmax) over ``batch["tokens"]``
    [B, T+1] plus the MoE router losses, each summed over layers:
    ``moe_aux_loss_coef`` × load balancing and ``moe_z_loss_coef`` ×
    the router z-loss; returns scalar."""
    tokens = batch["tokens"]
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    logits, aux = forward_with_aux(params, inp, cfg, mesh)
    with jax.named_scope("loss"):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        return nll.mean() + aux


# ---------------------------------------------------------------------------
# Train step factory
# ---------------------------------------------------------------------------

def make_train_step(cfg: TransformerConfig, mesh: Mesh, optimizer=None, *,
                    compression=None):
    """Build ``(init_state, step)``: a jitted SPMD training step over
    ``mesh`` — grads by ``jax.grad`` with GSPMD-inserted collectives
    (tp psums, fsdp reduce-scatters, dp allreduces all ride ICI), optax
    update, donated state.

    The Horovod-product analog of ``DistributedOptimizer`` +
    fused allreduce (``torch/optimizer.py:128``, ``operations.cc:943``)
    collapsed into one compiled program.

    ``compression`` (a ``hvd.Compression`` member; None/none = the
    exact pre-existing GSPMD step, bitwise unchanged) opts the
    data-plane gradient collectives into the quantized in-jit path
    (EQuARX). On a dp-only mesh the step is rebuilt as a ``shard_map``
    over ``dp`` with the model replicated per shard and gradients
    reduced by the blockwise int8/bf16 reduce-scatter + all-gather of
    ``ops/quantized.py``, int8 with rank-local error-feedback residuals
    carried in ``state["ef"]``. On a mesh with ``fsdp > 1`` the step
    becomes the partial-manual fsdp island
    (:func:`_make_fsdp_quantized_train_step`): params stay
    fsdp-sharded, the gradient reduce-scatter ships ``codec``-narrow
    bytes, and a second quantized hop covers ``dp`` when present.
    Scope: dp and fsdp are the gradient planes — tp/sp/pp/ep sharding
    has no gradient collective to intercept under GSPMD, so meshes with
    those axes > 1 raise.
    """
    import optax
    if optimizer is None:
        optimizer = optax.adamw(3e-4, weight_decay=0.01)

    from horovod_tpu import compression as compression_lib
    codec = compression_lib.in_jit_codec(compression)
    if codec != "none":
        _refuse_mixed(cfg, f"make_train_step(compression={codec!r})")
        return _make_quantized_train_step(cfg, mesh, optimizer,
                                          compression, codec)
    _refuse_mixed_off_dp(cfg, "make_train_step", mesh)

    def init_state(key):
        params = init_params(cfg, key)
        opt_state = optimizer.init(params)
        return {"params": params, "opt": opt_state, "step": jnp.zeros((), jnp.int32)}

    def step(state, batch):
        loss, grads = jax.value_and_grad(lm_loss)(
            state["params"], batch, cfg, mesh)
        with jax.named_scope("optimizer"):
            updates, new_opt = optimizer.update(
                grads, state["opt"], state["params"])
            params = optax.apply_updates(state["params"], updates)
        return {"params": params, "opt": new_opt,
                "step": state["step"] + 1}, loss

    param_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                            param_specs(cfg),
                            is_leaf=lambda x: isinstance(x, P))
    batch_sh = {"tokens": NamedSharding(mesh, P(("dp", "fsdp"), None))}
    init_state, jit_step = jit_sharded_state(
        init_state, step, mesh, param_sh, batch_sh, donate=True)
    return init_state, jit_step, param_sh


def jit_sharded_state(init, step, mesh: Mesh, param_sh, batch_sh=None, *,
                      ef_sh=None, donate: bool = False):
    """Jit ``init(key)`` and ``step(state, batch)`` with the train
    state's layout pinned on both sides: ``state["params"]`` to
    ``param_sh``, every optimizer-state leaf that mirrors a param (same
    tree position, same shape — Adam's moments) to that param's
    sharding, ``state["ef"]`` to ``ef_sh``, everything else replicated
    (counts, and statistics that share the params' tree but not their
    shapes, like Adafactor's factored rows and columns).

    The factories own the layout so no caller's spelling can replicate
    the state: an outer ``jax.jit(init_state)`` drops an in-trace
    ``device_put``, and a ``step`` that takes the state "as it finds
    it" then compiles FSDP in name only. ``step`` reshards whatever it
    is handed (a restored checkpoint) to the same layout.
    """
    repl = NamedSharding(mesh, P())
    p_struct = jax.tree.structure(param_sh)
    # The key is made inside the trace so the active PRNG
    # implementation (threefry, rbg) sets its shape.
    abstract = jax.eval_shape(lambda: init(jax.random.PRNGKey(0)))

    def like_params(node):
        return jax.tree.structure(node) == p_struct

    def pin(node):
        if not like_params(node):
            return repl
        return jax.tree.map(
            lambda leaf, param, sh: sh if leaf.shape == param.shape else repl,
            node, abstract["params"], param_sh)

    state_sh = {k: jax.tree.map(pin, v, is_leaf=like_params)
                for k, v in abstract.items()}
    if "ef" in abstract:
        state_sh["ef"] = ef_sh
    jit_init = jax.jit(init, out_shardings=state_sh)
    jit_step = jax.jit(step, donate_argnums=(0,) if donate else (),
                       in_shardings=(state_sh, batch_sh),
                       out_shardings=(state_sh, repl))
    return jit_init, jit_step


def _make_quantized_train_step(cfg: TransformerConfig, mesh: Mesh,
                               optimizer, compression, codec: str):
    """The ``compression=`` dispatcher of :func:`make_train_step`.

    Routes to the dp shard_map step (PR 9, byte-identical to before)
    or — when the mesh carries ``fsdp > 1`` — to the fsdp island
    below. Every other sharded axis raises: tp/sp/pp/ep collectives
    are activation-sized psums GSPMD inserts in the middle of the
    model, not gradient hops a codec could ride.
    """
    bad = [(ax, sz) for ax, sz in mesh.shape.items()
           if ax not in ("dp", "fsdp") and sz > 1]
    if bad:
        raise ValueError(
            f"make_train_step(compression={codec!r}) quantizes the "
            f"data-parallel gradient allreduce and the fsdp gradient "
            f"reduce-scatter; mesh axes {bad} have no explicit gradient "
            "collective to intercept under GSPMD. Use a dp/fsdp mesh, "
            "or compression=None for the GSPMD-sharded step.")
    if "dp" not in mesh.shape and mesh.shape.get("fsdp", 1) <= 1:
        raise ValueError(
            f"compression= needs a data axis ('dp', or 'fsdp' > 1); "
            f"mesh has {dict(mesh.shape)}")
    if mesh.shape.get("fsdp", 1) > 1:
        return _make_fsdp_quantized_train_step(cfg, mesh, optimizer,
                                               compression, codec)
    return _make_dp_quantized_train_step(cfg, mesh, optimizer,
                                         compression, codec)


def _make_dp_quantized_train_step(cfg: TransformerConfig, mesh: Mesh,
                                  optimizer, compression, codec: str):
    """The dp-only ``compression=`` body of :func:`make_train_step`.

    The GSPMD step has no interceptable dp gradient collective
    (autodiff of the global-mean loss reduces implicitly), so this
    variant makes the gradient plane explicit: one ``shard_map`` over
    the whole mesh runs the model replicated per dp shard on its local
    batch slice and reduces gradients with
    :func:`~horovod_tpu.ops.quantized.quantized_allreduce` — both hops
    of every gradient leaf ship ``codec``-narrow bytes, and int8
    threads per-rank error-feedback residuals as ``state["ef"]``
    leaves (globally ``[dp, *param.shape]`` f32, sharded ``P("dp")``,
    exactly the host plane's per-rank EF-slab shape discipline).
    """
    import optax

    from horovod_tpu import compression as compression_lib
    from horovod_tpu.common.ops_enum import Average
    from horovod_tpu.ops.quantized import quantized_allreduce

    ndp = mesh.shape["dp"]
    use_ef = compression_lib.needs_error_feedback(compression)

    def init_state(key):
        params = init_params(cfg, key)
        opt_state = optimizer.init(params)
        state = {"params": params, "opt": opt_state,
                 "step": jnp.zeros((), jnp.int32)}
        if use_ef:
            state["ef"] = jax.tree.map(
                lambda p: jnp.zeros((ndp,) + p.shape, jnp.float32), params)
        return state

    def shard_step(params, opt, ef, tokens):
        # Per dp shard: local batch slice, model built mesh-free (all
        # sharded axes are manual here; there is no GSPMD inside).
        def loss_fn(p):
            return lm_loss(p, {"tokens": tokens}, cfg, None)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        leaves, treedef = jax.tree.flatten(grads)
        if use_ef:
            ef_leaves = jax.tree.flatten(ef)[0]
            red, nef = [], []
            for g, r in zip(leaves, ef_leaves):
                y, nr = quantized_allreduce(g, op=Average, axis_name="dp",
                                            codec=codec, residual=r[0])
                red.append(y)
                nef.append(nr[None])
            grads = jax.tree.unflatten(treedef, red)
            ef = jax.tree.unflatten(treedef, nef)
        else:
            grads = jax.tree.unflatten(treedef, [
                quantized_allreduce(g, op=Average, axis_name="dp",
                                    codec=codec) for g in leaves])
        loss = lax.pmean(loss, "dp")
        # Identical (all-gathered) reduced grads on every shard ->
        # the replicated update keeps params bitwise in sync.
        with jax.named_scope("optimizer"):
            updates, opt = optimizer.update(grads, opt, params)
            params = optax.apply_updates(params, updates)
        return params, opt, ef, loss

    # check_vma=False: the reduced gradients leave quantized_allreduce
    # through an all_gather, which jax types as varying — the checker
    # cannot see that params/opt stay replicated (see ops/quantized.py).
    # With VMA off autodiff also leaves the gradients rank-local, which
    # is what the explicit reduction needs.
    smapped = jax.shard_map(
        shard_step, mesh=mesh,
        in_specs=(P(), P(), P("dp"), P("dp")),
        out_specs=(P(), P(), P("dp"), P()), check_vma=False)

    def step(state, batch):
        params, opt, ef, loss = smapped(
            state["params"], state["opt"], state.get("ef", {}),
            batch["tokens"])
        new_state = {"params": params, "opt": opt,
                     "step": state["step"] + 1}
        if use_ef:
            new_state["ef"] = ef
        return new_state, loss

    # Params replicated over dp (a dp-only mesh has no model sharding;
    # param_specs' tp/fsdp axes may not even exist here).
    param_sh = jax.tree.map(lambda s: NamedSharding(mesh, P()),
                            param_specs(cfg),
                            is_leaf=lambda x: isinstance(x, P))
    init_state, jit_step = jit_sharded_state(
        init_state, step, mesh, param_sh,
        ef_sh=NamedSharding(mesh, P("dp")))
    return init_state, jit_step, param_sh


def _fsdp_spec_dim(spec) -> Optional[int]:
    """Index of the ``fsdp``-sharded dimension in a PartitionSpec
    (None for fsdp-replicated leaves like the norms)."""
    for i, entry in enumerate(spec):
        if entry == "fsdp" or (isinstance(entry, tuple) and "fsdp" in entry):
            return i
    return None


def _make_fsdp_quantized_train_step(cfg: TransformerConfig, mesh: Mesh,
                                    optimizer, compression, codec: str):
    """The fsdp ``compression=`` body of :func:`make_train_step`.

    GSPMD's fsdp plane reduce-scatters gradients and all-gathers
    params with collectives it inserts itself — there is no hop a
    codec can ride. This variant expresses the fsdp step as a
    partial-manual ``shard_map`` island (manual over the data axes
    ``{dp, fsdp}``):

    * params stay fsdp-sharded on their ``param_specs`` dims (the
      ZeRO-3 layout; optimizer state and EF residuals shard with
      them), entering the island as local shards;
    * the forward all-gathers each sharded leaf over ``fsdp`` in the
      model dtype (the standard ZeRO param gather — already ≤ bf16
      for bf16 models, deliberately not lossy-quantized: param error
      has no EF to telescope through);
    * the gradient reduce-scatter is the explicit
      :func:`~horovod_tpu.ops.quantized.quantized_reduce_scatter`
      hop — quantize per destination shard → ``all_to_all`` →
      multiply-only f32 fold (psum_scatter-native for bf16/fp16);
      fsdp-replicated
      leaves (norms) ride a full ``quantized_allreduce`` over fsdp;
    * when the mesh also carries ``dp > 1``, a second
      ``quantized_allreduce`` hop over ``dp`` reduces each gradient
      shard across data-parallel groups (the requantize point — its
      hop-2 re-encode + narrow all-gather);
    * int8 error-feedback residuals are optimizer-state leaves
      ``state["ef"] = {"fsdp": ..., "dp": ...}``, leading dims
      ``[dp, fsdp]`` sharded ``P("dp", "fsdp")`` — per-rank slabs,
      the same contract as the dp path — with the dp-hop residuals
      shard-shaped (they compensate the post-scatter hop);
    * the optimizer update runs OUTSIDE the island on the sharded
      trees (pure elementwise; GSPMD keeps every leaf on its shard).
    """
    import optax

    from horovod_tpu import compression as compression_lib
    from horovod_tpu.common.ops_enum import Average
    from horovod_tpu.ops.quantized import (quantized_allreduce,
                                           quantized_reduce_scatter)

    nfsdp = mesh.shape["fsdp"]
    ndp = mesh.shape.get("dp", 1)
    dp_hop = ndp > 1
    batch_axes = tuple(ax for ax in ("dp", "fsdp") if ax in mesh.shape)
    lead = len(batch_axes)
    world_shape = tuple(mesh.shape[ax] for ax in batch_axes)
    use_ef = compression_lib.needs_error_feedback(compression)
    specs = param_specs(cfg)

    def _island_spec(spec):
        d = _fsdp_spec_dim(spec)
        return P(*[("fsdp" if i == d else None) for i in range(len(spec))])

    isl_specs = jax.tree.map(_island_spec, specs,
                             is_leaf=lambda x: isinstance(x, P))

    # Shard divisibility is a build-time contract (shard_map cannot pad
    # the way GSPMD does): every fsdp-sharded dim must divide by nfsdp.
    shapes = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), None))
    bad = []

    def _check_divisible(path, leaf, spec):
        d = _fsdp_spec_dim(spec)
        if d is not None and leaf.shape[d] % nfsdp:
            bad.append(f"{jax.tree_util.keystr(path)}{leaf.shape} dim {d}")
    jax.tree_util.tree_map_with_path(_check_divisible, shapes, specs)
    if bad:
        raise ValueError(
            f"make_train_step(compression={codec!r}): fsdp={nfsdp} does "
            f"not divide the sharded dim of {bad}; pad the model dims "
            "to multiples of the fsdp axis (the GSPMD path pads "
            "implicitly, the manual island cannot).")

    def island(p_shards, ef, tokens):
        params = jax.tree.map(
            lambda x, s: (lax.all_gather(x, "fsdp", axis=_fsdp_spec_dim(s),
                                         tiled=True)
                          if _fsdp_spec_dim(s) is not None else x),
            p_shards, specs)

        def loss_fn(p):
            return lm_loss(p, {"tokens": tokens}, cfg, None)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        g_leaves, treedef = jax.tree.flatten(grads)
        s_leaves = jax.tree.flatten(
            specs, is_leaf=lambda x: isinstance(x, P))[0]
        idx = (0,) * lead
        expand = (None,) * lead
        rs_res = (jax.tree.flatten(ef["fsdp"])[0] if use_ef
                  else [None] * len(g_leaves))
        dp_res = (jax.tree.flatten(ef["dp"])[0] if use_ef and dp_hop
                  else [None] * len(g_leaves))
        out, new_rs, new_dp = [], [], []
        for g, s, r1, r2 in zip(g_leaves, s_leaves, rs_res, dp_res):
            d = _fsdp_spec_dim(s)
            r1l = r1[idx] if r1 is not None else None
            if d is None:
                y = quantized_allreduce(g, op=Average, axis_name="fsdp",
                                        codec=codec, residual=r1l)
            else:
                y = quantized_reduce_scatter(g, op=Average,
                                             axis_name="fsdp", codec=codec,
                                             axis=d, residual=r1l)
            if r1l is not None:
                y, nr1 = y
                new_rs.append(nr1[expand])
            if dp_hop:
                r2l = r2[idx] if r2 is not None else None
                y = quantized_allreduce(y, op=Average, axis_name="dp",
                                        codec=codec, residual=r2l)
                if r2l is not None:
                    y, nr2 = y
                    new_dp.append(nr2[expand])
            out.append(y)
        grads = jax.tree.unflatten(treedef, out)
        new_ef = {}
        if use_ef:
            new_ef["fsdp"] = jax.tree.unflatten(treedef, new_rs)
            if dp_hop:
                new_ef["dp"] = jax.tree.unflatten(treedef, new_dp)
        for ax in batch_axes:
            loss = lax.pmean(loss, ax)
        return loss, grads, new_ef

    # Partial-manual: only the data axes are manual, anything else
    # rides auto/GSPMD.
    # check_vma=False: the VMA checker cannot infer a tiled
    # all_gather's output is replicated over the gathered axis (same
    # limitation as the embed island).
    smapped = jax.shard_map(
        island, mesh=mesh,
        in_specs=(isl_specs, P(*batch_axes), P(batch_axes)),
        out_specs=(P(), isl_specs, P(*batch_axes)),
        axis_names={"dp", "fsdp"} & set(mesh.axis_names), check_vma=False)

    def init_state(key):
        params = init_params(cfg, key)
        opt_state = optimizer.init(params)
        state = {"params": params, "opt": opt_state,
                 "step": jnp.zeros((), jnp.int32)}
        if use_ef:
            def z_full(p):
                return jnp.zeros(world_shape + p.shape, jnp.float32)

            def z_shard(p, s):
                d = _fsdp_spec_dim(s)
                shp = list(p.shape)
                if d is not None:
                    shp[d] //= nfsdp
                return jnp.zeros(world_shape + tuple(shp), jnp.float32)

            ef = {"fsdp": jax.tree.map(z_full, params)}
            if dp_hop:
                ef["dp"] = jax.tree.map(z_shard, params, specs)
            state["ef"] = ef
        return state

    def step(state, batch):
        loss, grads, new_ef = smapped(state["params"],
                                      state.get("ef", {}),
                                      batch["tokens"])
        with jax.named_scope("optimizer"):
            updates, new_opt = optimizer.update(grads, state["opt"],
                                                state["params"])
            params = optax.apply_updates(state["params"], updates)
        new_state = {"params": params, "opt": new_opt,
                     "step": state["step"] + 1}
        if use_ef:
            new_state["ef"] = new_ef
        return new_state, loss

    param_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), isl_specs,
                            is_leaf=lambda x: isinstance(x, P))
    init_state, jit_step = jit_sharded_state(
        init_state, step, mesh, param_sh,
        ef_sh=NamedSharding(mesh, P(*batch_axes)))
    return init_state, jit_step, param_sh
