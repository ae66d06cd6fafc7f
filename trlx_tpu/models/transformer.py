"""TPU-native causal transformer backbone (Flax linen).

One configurable decoder covers the reference's supported causal families —
GPT-2, GPT-J, GPT-NeoX/Pythia, OPT, BLOOM, LLaMA (reference wraps HF models:
``trlx/models/modeling_ppo.py:429-946``) — via architecture flags (positional
scheme, norm type, activation, parallel-residual, biases, GQA).

TPU-first design decisions:
- every weight carries **logical axis names** (``nn.with_logical_partitioning``)
  so one set of sharding rules (``trlx_tpu/parallel``) maps the whole model
  onto a ``(data, pipe, fsdp, model, sequence)`` mesh — the GSPMD equivalent of
  Megatron TP/SP in the reference's NeMo backend;
- **explicit functional KV cache** (a pytree threaded through the decode
  loop) instead of stateful modules, so generation is one compiled
  ``lax.while_loop`` program;
- static shapes everywhere: padding is handled by masks, positions are
  computed from the mask (left-padded prompts attend correctly);
- optional ``remat`` and ``scan_layers`` for memory/compile scaling.
"""

import contextlib
import dataclasses
import functools
import math
from functools import partial
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu.ops.cache_layout import cache_slots, lane_heads, lane_pack, lane_unpack


def param_with_axes(init: Callable, axes: Tuple[str, ...]) -> Callable:
    """Logical axes of each parameter are derived from its *path* by the rule
    table in ``trlx_tpu/parallel/sharding.py`` (path-based, à la t5x), so the
    param tree stays plain jax arrays (no flax Partitioned boxes) — plain
    trees keep the optimizer, HF interop, and checkpoint layers trivial. The
    ``axes`` argument documents intent at the definition site and is asserted
    against the rule table in tests."""
    del axes
    return init


def _maybe_pipeline_mesh(cfg: "TransformerConfig"):
    """The global mesh, iff its ``pipe`` axis should pipeline this model's
    block stack (requires ``scan_layers``: the stacked params are what shards
    across stages)."""
    from trlx_tpu.parallel.mesh import get_global_mesh

    mesh = get_global_mesh()
    if mesh is None or mesh.shape.get("pipe", 1) <= 1:
        return None
    if cfg.ignore_pipe_mesh:
        return None
    if not cfg.scan_layers:
        raise ValueError(
            "pipeline parallelism (mesh pipe>1) requires scan_layers=True — "
            "the stacked block params are what shards across stages"
        )
    if cfg.num_layers % mesh.shape["pipe"]:
        raise ValueError(
            f"num_layers {cfg.num_layers} not divisible by pipe stages "
            f"{mesh.shape['pipe']}"
        )
    return mesh


def _traced_global_mesh():
    """The global mesh, iff one is set AND we are inside a trace (sharding
    constraints / collective layouts only apply under jit; eager passes —
    e.g. ``module.init`` — take the plain paths)."""
    from jax._src.core import trace_state_clean

    from trlx_tpu.parallel.mesh import get_global_mesh

    mesh = get_global_mesh()
    if mesh is not None and not trace_state_clean():
        return mesh
    return None


def _activation_sharded(x):
    """Pin the weight-stationary decode layout on a ``[B, 1, D]`` embedding
    output: batch over ``data``, hidden over ``fsdp``, seq untouched. The
    hidden shards line up with the ``(fsdp, model)`` kernel sharding's
    contracted dim, so every block matmul in the decode loop is a local
    partial + a tiny ``[B,1,D]`` all-reduce and the multi-GB weights never
    move.

    Applied at the embedding output of single-token decode steps ONLY: the
    vocab-parallel ``wte`` gather otherwise leaves the partitioner free to
    pick a conflicting layout for the lookup result inside the decode
    ``while`` loop, which it then cannot reconcile with the loop body's
    layout without an involuntary full rematerialization
    (``spmd_partitioner.cc`` replicate-then-repartition) on every step. Full
    forwards (prefill / score / train) are deliberately left unconstrained —
    there the partitioner's propagated layout avoids per-layer fsdp weight
    all-gathers entirely (measured: constraining them trades -33% flops for
    +130% bytes_accessed on the 6B fsdp2·tp2·sp2 budget, a net loss on the
    HBM-bound programs), and no remat warning is emitted on those paths.
    """
    mesh = _traced_global_mesh()
    if mesh is None or x.ndim != 3 or x.shape[1] != 1:
        return x
    if mesh.shape.get("pipe", 1) > 1:
        # the pipeline engine re-lays activations into its stage-resident
        # [S, mb, T, E] buffer immediately after embed and constrains that
        # buffer itself (parallel/pipeline.py::tick); a conflicting spec here
        # just forces a reshard at the injection slice
        return x
    from trlx_tpu.parallel.sharding import constrain_activation

    return constrain_activation(x, mesh, "data", None, "fsdp")


def _maybe_ring_mesh(T: int):
    """The traced mesh, iff its ``sequence`` axis should carry this pass
    (full self-attention forwards, ALiBi included; ring doesn't apply to
    cache decode — plain flash handles that, with GSPMD gathering K/V if
    activations are sequence-sharded)."""
    mesh = _traced_global_mesh()
    if (
        mesh is not None
        and mesh.shape.get("sequence", 1) > 1
        and T % mesh.shape["sequence"] == 0
    ):
        return mesh
    return None


def _flash_attention(q, k, v, flash_args: Dict[str, Any]) -> jax.Array:
    """The fused flash kernel over ``[B, T, H, D]`` / ``[B, S, KV, D]``, on
    whatever mesh is live.

    GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"), so under a mesh of more than one device the
    call runs per shard inside a fully manual ``shard_map``, laid out by
    ``parallel/sharding.py::attention_shard_axes`` (batch and heads where
    the projections already put them, so no operand moves) and replicated
    over the remaining axes. Masking semantics are the additive-bias path's
    (slot-causal + key validity + optional window/ALiBi)."""
    from trlx_tpu.ops.flash_attention import flash_attention

    window = flash_args.get("window")
    # q_offset may be a traced scalar (prefill into a cache), so it rides as
    # an operand; window is static
    operands = {
        name: flash_args[name]
        for name in ("key_mask", "q_positions", "k_positions", "alibi_slopes", "selection")
        if flash_args.get(name) is not None
    }
    operands["q_offset"] = jnp.asarray(flash_args.get("q_offset", 0), jnp.int32)

    selection_block = flash_args.get("selection_block", 1)  # static, as the window

    def call(q, k, v, kw):
        return flash_attention(q, k, v, causal=True, window=window, selection_block=selection_block, **kw)

    mesh = _traced_global_mesh()
    if mesh is None or mesh.devices.size == 1:
        return call(q, k, v, operands)

    from jax.sharding import PartitionSpec as P

    from trlx_tpu.parallel.sharding import attention_shard_axes

    batch_axes, head_axis = attention_shard_axes(mesh, k.shape)
    heads = P(batch_axes, None, head_axis, None)
    rows = P(batch_axes, None)
    layouts = {
        "key_mask": rows,
        "q_positions": rows,
        "k_positions": rows,
        "alibi_slopes": P(head_axis),
        "q_offset": P(),
        "selection": P(batch_axes, None, None) if selection_block == 1 else P(batch_axes, head_axis, None, None),
    }
    return jax.shard_map(
        call,
        mesh=mesh,
        in_specs=(heads, heads, heads, {name: layouts[name] for name in operands}),
        out_specs=heads,
        check_vma=False,
    )(q, k, v, operands)


# The stand-in scale of q_proj and k_proj for `builtin:smallthinker-*`, run
# from random weights (TransformerConfig.qk_init_std): chosen once on the CPU
# at the published widths so that the window and the two rotary readings each
# move the float32 logits by several times the chip tolerance
# (chipbench/configs/smallthinker-21b-a3b-l4e16.json, `assumed`, has the
# measured numbers). A constant with its measurement, not a setting.
QK_INIT_STD_SMALLTHINKER = 0.04
# the like for K-EXAONE, whose per-head QK-norm takes it as the norms' scale
# (`_qk_norm`): 2 on q and on k, a score of standard deviation 4
# (chipbench/configs/k-exaone-236b-a23b-l5e8.json, `assumed`)
QK_INIT_STD_EXAONE = 0.04
# the like for MiniCPM-SALA, per-head norms again: 2 on q and on k of both kinds of
# layer (chipbench/configs/minicpm-sala-9b-l8.json, `assumed`)
QK_INIT_STD_MINICPM_SALA = 0.04
# ... and of its token embedding: 1 / scale_emb, so that the residual stream starts at unit size as a
# muP-trained embedding under `scale_emb` 12 does. At the 1.0 of the other stand-ins the stream is 12
# and a layer's two branches (x 0.2475) are a sixtieth of it: the first chip run read a float32
# reference 0.0068 away and no fault of a mixer could have read more than twice that
EMBED_INIT_STD_MINICPM_SALA = 1.0 / 12.0
# the like for Kimi-Linear's latent layers, whose `q_proj` makes the queries from the normed stream with
# no latent and no norm between (chipbench/configs/kimi-linear-48b-a3b-l8e32.json, `assumed`)
QK_INIT_STD_KIMI_LINEAR = 0.08
# the like for LFM2, per-head norms as K-EXAONE's: 2 on q and on k of the attention layers, a score of standard
# deviation 4 at a head of 64 (chipbench/configs/lfm2-8b-a1b-l10e8.json, `assumed`)
QK_INIT_STD_LFM2 = 0.04
# the like for Nemotron-H's attention layers, which have no norm on q or k and no rotary: q.k/sqrt(128) of standard
# deviation 4 at hidden 2688 (chipbench/configs/nemotron3-nano-30b-a3b-l9e8.json, `assumed`)
QK_INIT_STD_NEMOTRON_H = 0.04


class AttentionSizes(NamedTuple):
    """One layer's attention geometry (``TransformerConfig.attention_sizes``),
    the ONE answer every reader takes: ``LatentAttention``, ``make_kv_cache``,
    ``ops/cache_layout.py`` and the benchmark's counts. A window
    layer of a stack with ``swa_*`` sizes has its own; every other layer the
    model-wide ones (so an ``Indexer``, which only a full layer has, reads the
    model-wide ``qk_rope_head_dim``)."""

    heads: int
    q_lora_rank: int
    kv_lora_rank: int
    nope: int  # a latent head's no-rope q and k dims (a K/V head: all of `dims_per_head`)
    rope: int  # ... and its roped ones, the ONE shared key's size (a K/V head: 0)
    v: int
    theta: float
    window: Optional[int]


class LayerLayout(NamedTuple):
    """One layer's kind (``TransformerConfig.layer_layout``): its attention
    layout, which the bias, the flash arguments, the sampler's cache and the
    hydra branch all read, and its feed-forward kind, which ``Block`` reads."""

    window: Optional[int]  # a query sees its last `window` slots; None = full causal
    rotary: bool  # False: this layer applies no rotary embedding (NoPE)
    # dense (MLP of `intermediate_size`) | moe (MoEMLP of `expert_width`) | none (the layer is its
    # sequence mixer alone: one norm, one sublayer, one residual add; `ffn_layout`)
    ffn: str = "dense"
    # learned selection of keys (`index_topk` > 0): "full" = the layer has an
    # indexer of its own and selects; "shared" = it attends over the set the
    # last "full" layer before it chose; None = no selection
    indexer: Optional[str] = None
    # the layer's sequence mixer (`mixer_layout`): "attention" (`Attention`, or
    # `LatentAttention` under `kv_lora_rank`; where `mixer` is "mamba2", with
    # Mamba-2 heads BESIDE it in the block) | "lightning" (`LightningMixer`: a
    # linear recurrence whose state is the layer's whole cache, no K or V) |
    # "kda" (`KDAMixer`: a gated delta rule behind short convs; the state and
    # the convs' last rows are the layer's whole cache) | "conv"
    # (`ShortConvMixer`: a short causal conv between two gates; the conv's
    # last input rows are the layer's whole cache) | "mamba2" (`Mamba2Mixer`
    # as the layer's WHOLE mixer: its state and its conv's last rows are the
    # layer's whole cache, no K or V) | "none" (the layer is its feed-forward
    # part alone and caches nothing)
    mixer: str = "attention"


# the window layers' own sizes (`TransformerConfig.attention_sizes`)
SWA_FIELDS = ("swa_num_heads", "swa_q_lora_rank", "swa_kv_lora_rank", "swa_qk_nope_head_dim", "swa_qk_rope_head_dim",
              "swa_v_head_dim", "swa_rope_theta")
SPARSE_INIT_BLOCKS = 1  # leading blocks every query of a block selection keeps (MiniCPM4's init_blocks)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """Architecture description of a causal decoder-only transformer."""

    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    intermediate_size: int
    max_position_embeddings: int = 2048
    num_kv_heads: Optional[int] = None  # < num_heads → grouped-query attention
    head_dim: Optional[int] = None

    # HF family tag ("gpt2", "llama", "gpt_neox", "gptj", "opt", "bloom");
    # selects the import/export converter pair in hf_interop
    model_type: Optional[str] = None

    position_scheme: str = "learned"  # learned | rotary | alibi | none (no positional term anywhere: nemotron_h)
    pos_offset: int = 0  # OPT stores positions with an offset of 2
    rotary_dim: Optional[int] = None  # partial rotary (gptj/neox); None = full
    rope_theta: float = 10000.0

    # sliding-window attention (mistral family): each query attends only the
    # last `sliding_window` positions. None = unbounded full causal. Slots
    # are temporally ordered with padding only on the left, so the window is
    # enforced on slot distance in every path (xla bias, flash kernel, ring).
    sliding_window: Optional[int] = None
    # per-layer attention layout (smallthinker): two published lists of one
    # 0/1 entry a layer, read independently. `sliding_window_layout[l] = 0`
    # leaves layer l full causal whatever `sliding_window` says;
    # `rope_layout[l] = 0` leaves it without rotary embedding (NoPE). None =
    # every layer as the two scalars above say: a uniform layout is the same
    # definition with all ones, not a second path (`layer_layout`). Entries
    # past `num_layers` (a published list on a cut depth) are not read.
    sliding_window_layout: Optional[Tuple[int, ...]] = None
    rope_layout: Optional[Tuple[int, ...]] = None

    norm: str = "layernorm"  # layernorm | rmsnorm
    layer_norm_epsilon: float = 1e-5
    activation: str = "gelu_new"  # gelu_new | gelu | silu | relu | relu2 (`relu(x)^2`)
    parallel_residual: bool = False  # gptj/neox style
    shared_ln: bool = False  # gptj: one LN feeds both attn and mlp
    # RMSNorm with a learned scale on q and on k, before rotary. True (olmoe):
    # over the whole projected width, before the split into heads. "head"
    # (exaone_moe): over each head's own dims, one scale of the head's size
    # that every head of q (of k) shares
    qk_norm: Any = False  # False | True | "head"
    attn_bias: bool = True
    mlp_bias: bool = True
    qkv_bias: Optional[bool] = None  # overrides attn_bias for q/k/v if set
    tie_word_embeddings: bool = True
    final_norm: bool = True
    embedding_layernorm: bool = False  # bloom has a LN after word embeddings
    lm_head_bias: bool = False  # gptj has a bias on the lm head
    # std of the token embedding's random init (every other matrix: 0.02).
    # At 0.02 the attention output of a shared prefix outweighs the token's
    # own embedding in the residual stream, so a randomly initialised MoE
    # router sends all rows of a prompt group to the same few experts; at 1
    # (torch's nn.Embedding default) routing follows the token, as in a
    # trained MoE. Only matters for models run from random weights.
    embed_init_std: float = 0.02
    # std of q_proj's and k_proj's random init. At 0.02 and hidden 2560 the
    # scores q.k/sqrt(d) have a standard deviation of 1: the softmax is close
    # to flat, and leaving a window out moves the logits by less than a bf16
    # check can tell from rounding. Only matters for models run from random
    # weights
    qk_init_std: float = 0.02

    # numerics / compilation
    param_dtype: Any = jnp.float32
    dtype: Any = jnp.bfloat16  # activation/compute dtype
    remat: str = "none"  # none | minimal | full
    scan_layers: bool = False
    # run unpipelined even when the global mesh has pipe > 1: the model
    # computes replicated across pipeline stages instead of through the
    # GPipe schedule. For small auxiliary models that ride a big model's
    # mesh — e.g. the speculative-decoding draft, which runs replicated
    # while the pipelined target verifies its proposals.
    ignore_pipe_mesh: bool = False
    # attention implementation: "auto" (pallas flash kernel on TPU, xla
    # elsewhere), "xla" (dot-product, XLA-fused), or "pallas" (force flash)
    attention_impl: str = "auto"

    # LoRA (reference: OpenDelta lora via ``model.peft_kwargs``,
    # ``trlx/utils/modeling.py:389-450``). r=0 disables. Adapters are created
    # on every matching projection; the trainable mask keeps only the
    # unfrozen-layer range learnable, which matches the reference's
    # layer-ranged modified_modules regex with zero-init B making the rest
    # exact no-ops.
    lora_r: int = 0
    lora_alpha: float = 16.0
    lora_targets: Tuple[str, ...] = ()

    # pipeline parallelism: microbatches per GPipe round when the mesh has a
    # pipe axis > 1 (0 = auto: one per stage). See parallel/pipeline.py.
    pipe_microbatches: int = 0

    # mixture-of-experts MLP (mixtral, olmoe and smallthinker families; beyond
    # the reference, which has no MoE — SURVEY.md §2.3 lists EP as n/a). 0 =
    # dense MLP. `num_experts` is the ROUTER's width. With a capacity
    # (mixtral) experts are GShard-style einsum dispatch with a per-sequence
    # token group and a static capacity, all of them held, and their weights
    # shard over the mesh's `expert` axis (parallel/mesh.py) so XLA inserts
    # the token all_to_alls.
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # gated experts, `act(gate) * up` (SwiGLU with silu, ReGLU with relu):
    # said by the preset, never inferred from the activation's name
    moe_gated: bool = False
    # one chip's share of a deployment's experts (dropless routing only): the
    # layer holds experts [moe_first_expert, moe_first_expert +
    # moe_experts_held) of the router's `num_experts`, routes over all of
    # them, renormalises over all the chosen, and computes the part of the
    # result its own give. 0 = all held
    moe_experts_held: int = 0
    moe_first_expert: int = 0
    # what the router reads: "mlp_input" (the normed input of the experts,
    # behind attention) or "block_input" (the block's raw input, before the
    # input norm and before attention: smallthinker)
    moe_router_input: str = "mlp_input"
    # slots per expert = ceil(k*G*cf/E); 0 = no capacity bound (dropless:
    # every token is computed by all k of its experts, grouped matmuls over
    # the assignments sorted by expert; one chip only, no `expert` axis)
    moe_capacity_factor: float = 1.25
    moe_group_size: int = 0  # dispatch group tokens (0 = whole sequence);
    # bounds the [.., E, C] slot tensors to O(T·G) instead of O(T²)
    moe_renormalize: bool = True  # mixtral renormalizes the top-k gate probs
    # what the renormalisation divides by: 0 = `max(sum, 1e-9)`; above 0 the
    # chosen scores' `sum + moe_renormalize_eps` (lfm2_moe publishes 1e-6)
    moe_renormalize_eps: float = 0.0
    # the experts' own width where the stack also has dense layers of
    # `intermediate_size` (0 = `intermediate_size`: every family whose layers
    # are all sparse publishes one width)
    moe_intermediate_size: int = 0
    # the first `first_k_dense` layers are dense MLPs of `intermediate_size`,
    # the rest sparse (`layer_layout(i).ffn`); 0 with experts = every layer sparse
    first_k_dense: int = 0
    # experts every token passes beside the routed ones: ONE gated MLP of
    # `num_shared_experts * expert_width`, 2-D `kernel` leaves under
    # `mlp/shared_expert`, added to the routed result unweighted. Every chip
    # of a deployment computes it alike, so it counts once when held shares
    # are added up
    num_shared_experts: int = 0
    # that MLP's width where the family publishes one of its own (nemotron_h's
    # `moe_shared_expert_intermediate_size`); 0 = `num_shared_experts * expert_width`
    moe_shared_expert_intermediate_size: int = 0
    # how the router scores: "softmax" over the experts, or "sigmoid" of each
    # logit on its own; top-k runs on the scores either way
    moe_scoring: str = "softmax"
    routed_scaling_factor: float = 1.0  # multiplies the (renormalised) gates
    router_aux_coef: float = 0.01  # load-balance loss weight (Switch-style)
    router_z_coef: float = 0.0  # router logit z-loss weight (ST-MoE)

    # latent attention (pangu_ultra_moe; `kv_lora_rank` > 0 selects
    # `LatentAttention`): queries through a normed latent of `q_lora_rank`,
    # keys and values through a normed latent of `kv_lora_rank` plus ONE
    # roped key of `qk_rope_head_dim` shared by all heads. A head's q and k
    # are `qk_nope_head_dim + qk_rope_head_dim` wide (`dims_per_head`), its v
    # `v_head_dim`. The sampler's cache holds the latent and the roped key
    # (`make_kv_cache`), never per-head K and V.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: Optional[int] = None  # None = `dims_per_head`
    # a norm on each sublayer's OUTPUT as well as on its input, before the
    # residual add: x + N2(Attn(N1(x))), then a + N4(FFN(N3(a)))
    sandwich_norm: bool = False

    # learned sparse attention over the latent cache (glm_moe_dsa; `index_topk`
    # > 0, latent attention only): a query attends over the `index_topk` valid
    # causal slots with the largest index score `I[t, s] = sum_j w[t, j]
    # relu(qI[t, j] . kI[s])`, `index_heads` query heads of `index_head_dim`
    # from the query latent against ONE normed key a slot (`LatentAttention`).
    # `indexer_types[l]` says whether layer l has an indexer of its own
    # ("full") or borrows the last full layer's set ("shared"); None = every
    # layer full. Entries past `num_layers` are not read. The sampler's cache
    # holds the index keys of a full layer beside its latent (`make_kv_cache`).
    index_topk: int = 0
    index_heads: int = 0
    index_head_dim: int = 0
    indexer_types: Optional[Tuple[str, ...]] = None
    # how the router CHOOSES its experts: "greedy" = the largest scores;
    # "noaux_tc" = the largest of `score + bias`, a leaf `router_bias` of the
    # router's width that only a configuration naming it creates, while the
    # gates come from the scores without it
    moe_topk_method: str = "greedy"
    moe_bias_init_std: float = 0.0  # std of that bias's random init (a trained one balances load)

    # next-token-prediction modules behind the stack (exaone_moe's published
    # `num_nextn_predict_layers`; the DeepSeek-V3 report's module): module k is
    # a subtree `mtp_<k>` beside the `h_<i>` blocks, ONE more block fed
    # `eh_proj [RMSNorm(h_t) ; RMSNorm(Emb(x_{t+1}))]` (`h_t` the last layer's
    # output before the final norm), with a final norm of its own and the main
    # embedding and head: a distribution over `x_{t+2}`. Its layer kind is
    # `layer_layout(num_layers + k)` and its cache layer `make_kv_cache`'s entry
    # `num_layers + k`. Only `CausalTransformer.draft` runs it (the rollout
    # sampler's drafter, `ops/speculative.py`): the scoring forward, the hydra
    # branch and the train step never do, it takes no adapter and
    # `trainable_mask` freezes it. One module is built
    mtp_layers: int = 0

    # a second sequence mixer beside attention in every block (falcon_h1):
    # "mamba2" runs Mamba-2 heads and the attention heads on the SAME normed
    # input and adds both to the residual. Its per-sequence state (the
    # recurrent state, float32, and the conv's last rows) lives in the layer's
    # cache dict beside K and V (make_kv_cache). "none" = attention alone.
    mixer: str = "none"  # none | mamba2
    mamba_heads: int = 0
    mamba_head_dim: int = 0
    mamba_state: int = 0  # state size N of each head
    mamba_groups: int = 1  # B and C are shared by heads / groups heads
    mamba_conv: int = 4  # causal depthwise conv width over x, B, C
    mamba_chunk: int = 128  # chunk of the scan (ops/ssd.py)
    # std of the random init of `in_proj`'s z, x, B, C columns (`Mamba2Mixer`; the dt columns keep 0.02).
    # Only matters for models run from random weights: 0.5 under Falcon-H1's multipliers (0.25 x 0.18 to 0.5)
    mamba_in_proj_init_std: float = 0.5
    # the family's fixed scalar multipliers (muP forward scalings), all 1 for
    # every other family: applied only where they differ from 1
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    mlp_multipliers: Tuple[float, ...] = (1.0, 1.0)  # gate pre-activation, down output
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0,) * 5  # in_proj's z, x, B, C, dt segments
    # both sublayers' outputs times this before the residual add (minicpm_sala:
    # `scale_depth / sqrt(num_hidden_layers)` of the PUBLISHED depth)
    residual_multiplier: float = 1.0

    # each layer's sequence mixer (minicpm_sala's `mixer_types`): one entry a
    # layer, "attention" | "lightning" | "kda" | "conv" (`LayerLayout.mixer`);
    # None = attention on every layer. Entries past `num_layers` are not read. A lightning
    # layer runs `lightning_heads` heads of `lightning_head_dim` (q, k and v
    # alike) through `LightningMixer`
    mixer_layout: Optional[Tuple[str, ...]] = None
    # each layer's feed-forward kind, one entry a layer: "dense" | "moe" | "none"
    # (`LayerLayout.ffn`); None = `first_k_dense` dense layers, then experts
    # where the model has any. A layer may lack its mixer ("none" in
    # `mixer_layout`) or its feed-forward part, never both: it then is ONE
    # norm, ONE sublayer, ONE residual add (`Block`)
    ffn_layout: Optional[Tuple[str, ...]] = None
    # nemotron_h's published `hybrid_override_pattern`, one letter a layer: `M`
    # a Mamba-2 mixer alone, `*` attention alone, `E` the experts alone. Set,
    # it IS the two layouts above (`__post_init__` derives both from it), and a
    # cut of the depth reads its first `num_layers` letters
    hybrid_override_pattern: Optional[str] = None
    lightning_heads: int = 0
    lightning_head_dim: int = 0
    lightning_chunk: int = 128  # chunk of the scan (ops/ssd.py)
    # a sigmoid gate of a full `hidden x heads*head_dim` projection (`z_proj`)
    # on the attention layers' output, before `o_proj`
    attn_output_gate: bool = False
    # block-sparse attention over a plain GQA cache (minicpm_sala's `minicpm4`
    # layers, InfLLM-V2; `sparse_topk` > 0, on every `attention` layer): a
    # query attends over `sparse_topk` blocks of `sparse_block` keys, chosen
    # for all the query heads of a KV group from the keys' running mean-pool
    # (`select_blocks`); a row of under `sparse_dense_len` slots attends
    # densely. Positions count from a row's first real token
    sparse_topk: int = 0
    sparse_block: int = 64
    sparse_kernel: int = 32  # keys a compressed key averages
    sparse_stride: int = 16  # ... and the step between two of them
    sparse_window: int = 2048  # a query keeps every block with a key this close behind it, and block 0
    sparse_dense_len: int = 8192
    # Kimi Delta Attention layers (kimi_linear's `linear_attn_config`; the
    # "kda" entries of `mixer_layout`, beside latent or K/V attention layers):
    # `kda_heads` heads of `kda_head_dim` (q, k and v alike; the two gates'
    # low rank too) behind causal depthwise convs of width `kda_conv`, through
    # `KDAMixer` and `ops/delta_rule.py`
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4
    # gated short convolution layers (lfm2_moe; the "conv" entries of
    # `mixer_layout`, beside K/V attention layers): `ShortConvMixer`, a causal
    # depthwise conv of `conv_L_cache` taps over the hidden width between two
    # elementwise gates; `conv_bias` is the published key (false: no bias on the
    # two projections or the conv; true is refused, no model here has one)
    conv_L_cache: int = 3
    conv_bias: bool = False
    # a second attention geometry on the WINDOW layers of a latent stack
    # (dots3_note's `swa_*` keys): each None = as the model-wide field of the
    # like name, which the full layers keep (`attention_sizes`). With
    # `index_topk` the full layers select for themselves and a window layer
    # selects nothing and holds no index keys (`layer_layout`); its cache is a
    # ring of `sliding_window` latents (`make_kv_cache`)
    swa_num_heads: Optional[int] = None
    swa_q_lora_rank: Optional[int] = None
    swa_kv_lora_rank: Optional[int] = None
    swa_qk_nope_head_dim: Optional[int] = None
    swa_qk_rope_head_dim: Optional[int] = None
    swa_v_head_dim: Optional[int] = None
    swa_rope_theta: Optional[float] = None
    # "headwise": a sigmoid gate a HEAD on a latent layer's output before
    # `o_proj`, `g = sigmoid(x Wgate)`, `Wgate` `hidden x heads` from the
    # layer's normed input (`head_gate`; `attn_output_gate` above is the
    # full-width one of the K/V layers)
    attention_gate_type: Optional[str] = None
    # the two normed latents times `sqrt(hidden_size / rank)`, in both forms
    # of `LatentAttention` and in its cache
    mla_lora_rescale: bool = False

    def resolved_attention_impl(self) -> str:
        if self.attention_impl == "auto":
            return "pallas" if jax.default_backend() == "tpu" else "xla"
        return self.attention_impl

    def __post_init__(self):
        for name in ("sliding_window_layout", "rope_layout"):
            value = getattr(self, name)
            if value is not None:  # a list from a JSON override
                value = tuple(int(x) for x in value)
                if len(value) < self.num_layers:
                    raise ValueError(f"{name} has {len(value)} entries for {self.num_layers} layers")
                object.__setattr__(self, name, value)
        if self.hybrid_override_pattern is not None:
            letters = {"M": ("mamba2", "none"), "*": ("attention", "none"), "E": ("none", "moe")}
            pattern = str(self.hybrid_override_pattern)
            if set(pattern) - set(letters):
                raise ValueError(f"hybrid_override_pattern takes the letters M (Mamba-2), * (attention) and E (experts): {pattern!r}")
            object.__setattr__(self, "mixer_layout", tuple(letters[c][0] for c in pattern))
            object.__setattr__(self, "ffn_layout", tuple(letters[c][1] for c in pattern))
        if self.ffn_layout is not None:
            ffns = tuple(str(f) for f in self.ffn_layout)
            mixers = self.mixer_layout or ("attention",) * len(ffns)
            if len(ffns) < self.num_layers or set(ffns[: self.num_layers]) - {"dense", "moe", "none"} or (
                    "moe" in ffns[: self.num_layers] and self.num_experts < 1):
                raise ValueError(f"ffn_layout needs one of dense | moe (with num_experts) | none for each of {self.num_layers} layers: {ffns}")
            empty = [i for i in range(min(self.num_layers, len(mixers))) if ffns[i] == "none" and mixers[i] == "none"]
            if empty:
                raise ValueError(f"layers {empty} have neither a sequence mixer (mixer_layout) nor a feed-forward part (ffn_layout): a layer is one or both")
            object.__setattr__(self, "ffn_layout", ffns)
        if self.mixer_layout is not None:
            kinds = tuple(str(m) for m in self.mixer_layout)
            used = set(kinds[: self.num_layers])
            if len(kinds) < self.num_layers or used - {"attention", "lightning", "kda", "conv", "mamba2", "none"}:
                raise ValueError(f"mixer_layout needs one of attention | lightning | kda | conv | mamba2 | none for each of {self.num_layers} layers: {kinds}")
            if self.mixer != "none" or self.mtp_layers or (
                    "lightning" in used and (self.latent_attention or self.lightning_heads < 1 or self.lightning_head_dim < 2)):
                raise ValueError(
                    "mixer_layout (lightning layers among attention layers) takes K/V attention layers, no second mixer "
                    "beside them, no next-token-prediction module, and lightning_heads heads of lightning_head_dim"
                )
            if "kda" in used and (self.index_topk or self.sparse_topk or self.kda_heads < 1 or self.kda_head_dim < 2 or self.kda_conv < 2):
                raise ValueError(
                    "mixer_layout (kda layers among attention layers) takes latent or K/V attention layers under no "
                    "selection, kda_heads heads of kda_head_dim and a conv of kda_conv >= 2 taps"
                )
            if "conv" in used and (self.conv_L_cache < 2 or self.conv_bias):
                raise ValueError("mixer_layout (conv layers among attention layers) takes a conv of conv_L_cache >= 2 taps and no conv_bias")
            if "mamba2" in used and (self.latent_attention or min(self.mamba_heads, self.mamba_head_dim, self.mamba_state, self.mamba_groups) < 1
                                     or self.mamba_heads % self.mamba_groups or self.mamba_conv < 2):
                raise ValueError(
                    "mixer_layout (mamba2 layers among attention layers) takes K/V attention layers, mamba_heads heads of "
                    "mamba_head_dim in mamba_groups groups of whole heads, a state of mamba_state and a conv of mamba_conv >= 2 taps"
                )
            if "none" in used and self.ffn_layout is None:
                raise ValueError("mixer_layout `none` (a layer without a sequence mixer) needs ffn_layout to say the layer's feed-forward part")
            object.__setattr__(self, "mixer_layout", kinds)
        lone = any("none" in (layout or ())[: self.num_layers] for layout in (self.mixer_layout, self.ffn_layout))
        if lone and (self.parallel_residual or self.sandwich_norm or self.moe_router_input != "mlp_input"):
            raise ValueError(
                "a layer of ONE sublayer (a `none` in mixer_layout or ffn_layout) takes the sequential residual path: no "
                "parallel_residual, no sandwich_norm, and a router on its own normed input"
            )
        if self.sparse_topk:
            b, k, s = self.sparse_block, self.sparse_kernel, self.sparse_stride
            if (self.latent_attention or self.sliding_window or self.position_scheme == "alibi" or min(b, k, s) < 1 or b % s or k % s
                    or self.sparse_topk <= SPARSE_INIT_BLOCKS + -(-self.sparse_window // b) + 1):
                raise ValueError(
                    "a block selection (sparse_topk > 0) runs over a plain K/V cache, no sliding window, no ALiBi, "
                    "blocks and kernels of whole strides, and more blocks than the forced ones "
                    f"(init {SPARSE_INIT_BLOCKS} + those of the window {self.sparse_window} over {b})"
                )
        if self.sandwich_norm and (self.parallel_residual or self.mixer != "none"):
            raise ValueError("sandwich_norm is built for the sequential residual path only")
        if self.kv_lora_rank and (self.qk_norm or self.position_scheme != "rotary" or self.mixer != "none"):
            raise ValueError("latent attention (kv_lora_rank > 0) takes rotary positions, no qk_norm, no second mixer")
        if self.qk_norm not in (False, True, "head"):
            raise ValueError(f"qk_norm {self.qk_norm!r} is not False, True (the whole projected width) or 'head'")
        if self.mtp_layers not in (0, 1) or (self.mtp_layers and (self.latent_attention or self.mixer != "none" or self.scan_layers)):
            raise NotImplementedError(
                "mtp_layers: ONE next-token-prediction module is built, behind a stack of K/V attention layers run "
                "unscanned; several modules chained, a module under a latent cache or beside a recurrent state are "
                "not (ROADMAP.md queue 2, B6)"
            )
        if self.moe_topk_method not in ("greedy", "noaux_tc"):
            raise ValueError(f"moe_topk_method {self.moe_topk_method!r} is not greedy or noaux_tc")
        swa = [name for name in SWA_FIELDS if getattr(self, name) is not None]
        if swa and not (self.kv_lora_rank and self.sliding_window):
            raise ValueError(f"the window layers' own sizes ({swa}) take latent attention (kv_lora_rank > 0) and a sliding_window")
        if self.attention_gate_type not in (None, "headwise") or (
                self.attention_gate_type and (not self.kv_lora_rank or "kda" in (self.mixer_layout or ()))):
            raise ValueError(f"attention_gate_type {self.attention_gate_type!r}: None or 'headwise', on latent attention "
                             "and no kda layers beside it (the K/V layers' full-width gate is attn_output_gate)")
        if self.mla_lora_rescale and not self.kv_lora_rank:
            raise ValueError("mla_lora_rescale scales the normed latents of latent attention (kv_lora_rank > 0)")
        if self.index_topk:
            # beside a window the FULL layers select, each for itself, and a window layer selects nothing
            # (`layer_layout`): a set borrowed across window layers is not built
            windowed = bool(self.sliding_window) and (self.sliding_window_layout is None or self.indexer_types is not None)
            if (not self.kv_lora_rank or not self.q_lora_rank or windowed or self.index_head_dim < self.qk_rope_head_dim
                    or self.index_heads < 1):
                raise ValueError(
                    "a learned selection (index_topk > 0) runs over a latent cache (kv_lora_rank > 0) from a query "
                    "latent (q_lora_rank > 0), with index_heads >= 1 heads of index_head_dim >= qk_rope_head_dim; beside a "
                    "sliding window only where sliding_window_layout leaves layers full, which then select for "
                    "themselves (no indexer_types: a window layer neither selects nor borrows)"
                )
            types = self.indexer_types
            if types is not None:  # a list from a JSON override
                types = tuple(str(t) for t in types)
                if len(types) < self.num_layers or set(types[: self.num_layers]) - {"full", "shared"} or types[0] != "full":
                    raise ValueError(
                        f"indexer_types needs one of full | shared for each of {self.num_layers} layers, "
                        f"the first full (a shared layer borrows the last full layer's set): {types}"
                    )
                object.__setattr__(self, "indexer_types", types)

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    def layer_layout(self, layer: int) -> LayerLayout:
        if layer >= self.num_layers:
            # a next-token-prediction module's block (`mtp_layers`): one of the stack's
            # global layers, so without rotary wherever the stack's global layers have none
            return LayerLayout(
                window=None,
                rotary=self.position_scheme == "rotary" and self.rope_layout is None,
                ffn="moe" if self.num_experts > 0 else "dense",
            )
        windowed = self.sliding_window_layout is None or bool(self.sliding_window_layout[layer])
        roped = self.rope_layout is None or bool(self.rope_layout[layer])
        ffn = "moe" if self.num_experts > 0 and layer >= self.first_k_dense else "dense"
        return LayerLayout(
            window=self.sliding_window if windowed and self.sliding_window else None,
            rotary=self.position_scheme == "rotary" and roped,
            ffn=self.ffn_layout[layer] if self.ffn_layout else ffn,
            indexer=(self.indexer_types[layer] if self.indexer_types else "full")
            if self.index_topk and not (windowed and self.sliding_window) else None,
            **({"mixer": self.mixer_layout[layer]} if self.mixer_layout else {}),
        )

    def attention_sizes(self, layer: int) -> AttentionSizes:
        """Layer ``layer``'s attention geometry: the model-wide sizes, and on a
        window layer each ``swa_*`` size that is set in its place."""
        window = self.layer_layout(layer).window
        latent = self.latent_attention

        def own(name, wide):
            value = getattr(self, name) if window else None
            return wide if value is None else value

        return AttentionSizes(
            heads=own("swa_num_heads", self.num_heads),
            q_lora_rank=own("swa_q_lora_rank", self.q_lora_rank),
            kv_lora_rank=own("swa_kv_lora_rank", self.kv_lora_rank),
            nope=own("swa_qk_nope_head_dim", self.qk_nope_head_dim) if latent else self.dims_per_head,
            rope=own("swa_qk_rope_head_dim", self.qk_rope_head_dim) if latent else 0,
            v=own("swa_v_head_dim", self.v_dims_per_head),
            theta=own("swa_rope_theta", self.rope_theta),
            window=window,
        )

    @property
    def layer_types(self) -> List[str]:
        return ["sliding_attention" if layout.window else "full_attention" for layout in self.layer_layouts]

    @property
    def layer_layouts(self) -> Tuple[LayerLayout, ...]:
        return tuple(self.layer_layout(i) for i in range(self.num_layers))

    @property
    def mixed_layout(self) -> bool:
        return len(set(self.layer_layouts)) > 1

    @property
    def experts_held(self) -> int:
        return self.moe_experts_held or self.num_experts

    @property
    def expert_width(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def latent_attention(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def dims_per_head(self) -> int:
        """A head's q and k size (a latent head's no-rope and rope parts together)."""
        if self.latent_attention:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def v_dims_per_head(self) -> int:
        return self.v_head_dim or self.dims_per_head

    @property
    def mamba_d_ssm(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def mamba_conv_channels(self) -> int:
        """x, B and C, the channels the conv runs over."""
        return self.mamba_d_ssm + 2 * self.mamba_groups * self.mamba_state

    # ---- family presets (sizes per the public model cards) ----

    @staticmethod
    def gpt2(size: str = "small", **overrides) -> "TransformerConfig":
        dims = {
            "test": dict(vocab_size=259, hidden_size=64, num_layers=2, num_heads=4, intermediate_size=256, max_position_embeddings=128),
            "small": dict(vocab_size=50257, hidden_size=768, num_layers=12, num_heads=12, intermediate_size=3072, max_position_embeddings=1024),
            "medium": dict(vocab_size=50257, hidden_size=1024, num_layers=24, num_heads=16, intermediate_size=4096, max_position_embeddings=1024),
            "large": dict(vocab_size=50257, hidden_size=1280, num_layers=36, num_heads=20, intermediate_size=5120, max_position_embeddings=1024),
            "xl": dict(vocab_size=50257, hidden_size=1600, num_layers=48, num_heads=25, intermediate_size=6400, max_position_embeddings=1024),
        }[size]
        return _make_preset(
            dims,
            overrides,
            model_type="gpt2",
            position_scheme="learned",
            norm="layernorm",
            activation="gelu_new",
            tie_word_embeddings=True,
        )

    @staticmethod
    def llama(size: str = "7b", **overrides) -> "TransformerConfig":
        dims = {
            "test": dict(vocab_size=259, hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=2, intermediate_size=128, max_position_embeddings=128),
            "7b": dict(vocab_size=32000, hidden_size=4096, num_layers=32, num_heads=32, intermediate_size=11008, max_position_embeddings=2048),
            "13b": dict(vocab_size=32000, hidden_size=5120, num_layers=40, num_heads=40, intermediate_size=13824, max_position_embeddings=2048),
            "65b": dict(vocab_size=32000, hidden_size=8192, num_layers=80, num_heads=64, intermediate_size=22016, max_position_embeddings=2048),
        }[size]
        return _make_preset(
            dims,
            overrides,
            model_type="llama",
            position_scheme="rotary",
            norm="rmsnorm",
            layer_norm_epsilon=1e-6,
            activation="silu",
            attn_bias=False,
            mlp_bias=False,
            tie_word_embeddings=False,
        )

    @staticmethod
    def mistral(size: str = "7b", **overrides) -> "TransformerConfig":
        dims = {
            "test": dict(vocab_size=259, hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=2, intermediate_size=128, max_position_embeddings=128, sliding_window=8),
            "7b": dict(vocab_size=32000, hidden_size=4096, num_layers=32, num_heads=32, num_kv_heads=8, intermediate_size=14336, max_position_embeddings=32768, sliding_window=4096),
        }[size]
        return _make_preset(
            dims,
            overrides,
            model_type="mistral",
            position_scheme="rotary",
            norm="rmsnorm",
            layer_norm_epsilon=1e-5,
            activation="silu",
            attn_bias=False,
            mlp_bias=False,
            tie_word_embeddings=False,
        )

    @staticmethod
    def mixtral(size: str = "8x7b", **overrides) -> "TransformerConfig":
        dims = {
            "test": dict(vocab_size=259, hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=2, intermediate_size=96, max_position_embeddings=128, num_experts=4),
            "8x7b": dict(vocab_size=32000, hidden_size=4096, num_layers=32, num_heads=32, num_kv_heads=8, intermediate_size=14336, max_position_embeddings=32768, num_experts=8, moe_group_size=512),
        }[size]
        return _make_preset(
            dims,
            overrides,
            model_type="mixtral",
            position_scheme="rotary",
            rope_theta=1e6,
            norm="rmsnorm",
            layer_norm_epsilon=1e-5,
            activation="silu",
            attn_bias=False,
            mlp_bias=False,
            tie_word_embeddings=False,
            num_experts_per_tok=2,
            moe_gated=True,
        )

    @staticmethod
    def olmoe(size: str = "1b-7b", **overrides) -> "TransformerConfig":
        dims = {
            "test": dict(vocab_size=259, hidden_size=64, num_layers=2, num_heads=4, intermediate_size=48, max_position_embeddings=128, num_experts=8, num_experts_per_tok=2),
            "1b-7b": dict(vocab_size=50304, hidden_size=2048, num_layers=16, num_heads=16, num_kv_heads=16, intermediate_size=1024, max_position_embeddings=4096, num_experts=64, num_experts_per_tok=8),
        }[size]
        return _make_preset(
            dims,
            overrides,
            model_type="olmoe",
            position_scheme="rotary",
            rope_theta=10000.0,
            norm="rmsnorm",
            layer_norm_epsilon=1e-5,
            activation="silu",
            attn_bias=False,
            mlp_bias=False,
            tie_word_embeddings=False,
            qk_norm=True,
            moe_gated=True,
            moe_capacity_factor=0.0,  # dropless, as published
            moe_renormalize=False,  # norm_topk_prob: false
            router_aux_coef=0.01,
        )

    @staticmethod
    def smallthinker(size: str = "21b-a3b", **overrides) -> "TransformerConfig":
        """SmallThinker-21BA3B-Instruct (PowerInfer): layers 1, 2, 3 of every
        four attend through a window of 4096 with rotary embedding, layers 0,
        4, 8, ... attend globally with no positional encoding at all; the
        router reads the block's raw input; 64 ReGLU experts of 768, top 6
        renormalised. Limits: the plain sampler, the scoring forward and the
        train step run a row longer than the window (window layers then keep
        a ring of ``sliding_window`` slots); slot refill, the paged Engine,
        the prefix cache and speculation only while no layer's cache is
        shorter than the row (``ops/cache_layout.py::refuse``); no
        ``scan_layers``, no HF checkpoint import. ``qk_init_std`` is this
        preset's stand-in scale for q_proj and k_proj
        (chipbench/configs/smallthinker-21b-a3b-l4e16.json, `assumed`)."""
        period = (0, 1, 1, 1)
        dims = {
            "test": dict(vocab_size=259, hidden_size=64, num_layers=4, num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=32, max_position_embeddings=128,
                         num_experts=8, num_experts_per_tok=3, sliding_window=8, sliding_window_layout=period, rope_layout=period),
            "21b-a3b": dict(vocab_size=151936, hidden_size=2560, num_layers=52, num_heads=28, num_kv_heads=4, head_dim=128, intermediate_size=768, max_position_embeddings=16384,
                            num_experts=64, num_experts_per_tok=6, sliding_window=4096, sliding_window_layout=period * 13, rope_layout=period * 13),
        }[size]
        return _make_preset(
            dims,
            overrides,
            model_type="smallthinker",
            position_scheme="rotary",
            rope_theta=1.5e6,
            norm="rmsnorm",
            layer_norm_epsilon=1e-6,
            activation="relu",
            attn_bias=False,
            mlp_bias=False,
            tie_word_embeddings=False,
            moe_gated=True,
            moe_router_input="block_input",
            moe_capacity_factor=0.0,  # no capacity bound
            moe_renormalize=True,  # norm_topk_prob: true
            router_aux_coef=0.0,  # the config publishes no balance loss
            embed_init_std=1.0,
            qk_init_std=QK_INIT_STD_SMALLTHINKER,
        )

    @staticmethod
    def pangu(size: str = "ultra-moe-718b", **overrides) -> "TransformerConfig":
        """openPangu-Ultra-MoE-718B (``model_type`` ``pangu_ultra_moe``):
        latent attention (``LatentAttention``), ``first_k_dense`` leading dense
        SwiGLU layers, then layers of 256 routed SwiGLU experts (sigmoid
        scores, top 8 renormalised, times 2.5) beside one shared expert, a
        norm after each sublayer as well as before (``sandwich_norm``). The
        next-token-prediction module is not built. Limits: the plain sampler,
        the scoring forward, the hydra branch and the train step only
        (``ops/cache_layout.py::refuse``); no ``scan_layers`` (two
        kinds of layer), no ring attention over ``sequence``, no HF checkpoint
        import. ``builtin:pangu-ultra-moe-718b`` | ``builtin:pangu-test``."""
        dims = {
            # unlike sizes everywhere a test can tell them apart: q/k 24 = 16 + 8, v 16, two latents
            "test": dict(vocab_size=259, hidden_size=64, num_layers=3, num_heads=4, intermediate_size=128, max_position_embeddings=128,
                         q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                         moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2, first_k_dense=1),
            "ultra-moe-718b": dict(vocab_size=153600, hidden_size=7680, num_layers=61, num_heads=128, num_kv_heads=128, intermediate_size=18432, max_position_embeddings=131072,
                                   q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                                   moe_intermediate_size=2048, num_experts=256, num_experts_per_tok=8, first_k_dense=3),
        }[size]
        return _make_preset(
            dims,
            overrides,
            model_type="pangu_ultra_moe",
            position_scheme="rotary",
            rope_theta=25.6e6,
            norm="rmsnorm",
            layer_norm_epsilon=1e-5,
            activation="silu",
            attn_bias=False,
            mlp_bias=False,
            tie_word_embeddings=False,
            sandwich_norm=True,
            num_shared_experts=1,
            moe_gated=True,
            moe_scoring="sigmoid",
            routed_scaling_factor=2.5,
            moe_capacity_factor=0.0,  # dropless
            moe_renormalize=True,  # norm_topk_prob: true
            router_aux_coef=0.0,  # the config publishes no balance loss
            embed_init_std=1.0,
        )

    @staticmethod
    def glm(size: str = "5.2", **overrides) -> "TransformerConfig":
        """GLM-5.2 (``model_type`` ``glm_moe_dsa``): latent attention
        (``LatentAttention``) under a learned selection of ``index_topk``
        keys a query, which a ``full`` layer's indexer makes and the
        ``shared`` layers after it borrow (``indexer_types``);
        ``first_k_dense`` leading dense SwiGLU layers, then layers of 256
        routed SwiGLU experts (sigmoid scores, the eight largest of ``score +
        bias``, renormalised, times 2.5) beside one shared expert. The
        next-token-prediction module is not built. Limits: the plain sampler,
        the scoring forward, the hydra branch and the train step only
        (``ops/cache_layout.py::refuse``); no ``scan_layers`` and no
        pipeline schedule (neither carries a selection from layer to layer),
        no ring attention over ``sequence``, no HF checkpoint import.
        ``builtin:glm-5.2`` | ``builtin:glm-test``."""
        period = ("full", "shared", "shared", "shared")
        dims = {
            # unlike sizes everywhere a test can tell them apart: q/k 20 = 12 + 8, v 16, index heads of 12,
            # a selection of 8 keys that binds on any row past 8 tokens, a period and a full layer behind it
            "test": dict(vocab_size=259, hidden_size=64, num_layers=5, num_heads=4, intermediate_size=128, max_position_embeddings=128,
                         q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16,
                         index_topk=8, index_heads=2, index_head_dim=12, indexer_types=period + ("full",),
                         moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2, first_k_dense=1, moe_bias_init_std=0.05),
            "5.2": dict(vocab_size=154880, hidden_size=6144, num_layers=78, num_heads=64, num_kv_heads=64, intermediate_size=12288, max_position_embeddings=1048576,
                        q_lora_rank=2048, kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
                        index_topk=2048, index_heads=32, index_head_dim=128, indexer_types=("full", "full") + period * 19,
                        moe_intermediate_size=2048, num_experts=256, num_experts_per_tok=8, first_k_dense=3),
        }[size]
        return _make_preset(
            dims,
            overrides,
            model_type="glm_moe_dsa",
            position_scheme="rotary",
            rope_theta=8e6,
            norm="rmsnorm",
            layer_norm_epsilon=1e-5,
            activation="silu",
            attn_bias=False,
            mlp_bias=False,
            tie_word_embeddings=False,
            num_shared_experts=1,
            moe_gated=True,
            moe_scoring="sigmoid",
            moe_topk_method="noaux_tc",
            routed_scaling_factor=2.5,
            moe_capacity_factor=0.0,  # dropless
            moe_renormalize=True,  # norm_topk_prob: true
            router_aux_coef=0.0,  # balance is the selection bias's work, not a loss's
            embed_init_std=1.0,
        )

    @staticmethod
    def exaone(size: str = "236b-a23b", **overrides) -> "TransformerConfig":
        """K-EXAONE-236B-A23B (``model_type`` ``exaone_moe``): GQA with an
        RMSNorm on each head's q and k (``qk_norm: "head"``); three layers in
        four attend through a window of 128 with rotary embedding, the fourth
        globally with none; one leading dense SwiGLU layer, then layers of 128
        routed SwiGLU experts (sigmoid scores, top 8 renormalised, times 2.5)
        beside one shared expert; ONE next-token-prediction module
        (``mtp_layers``), with which the model drafts its own rollouts
        (``ops/speculative.py``). Limits: the rollout sampler (plain or
        self-drafting: a window layer keeps a ring of ``window + 1`` slots),
        the scoring forward, the hydra branch and the train step; no slot
        refill, paged Engine or prefix cache while a layer's cache is shorter
        than the row (``ops/cache_layout.py::refuse``); no
        ``scan_layers``, no HF checkpoint import. ``qk_init_std`` is the
        stand-in scale of the scores, which under a per-head norm lives in the
        norms' scales (``_qk_norm``; chipbench/configs/k-exaone-236b-a23b-l5e8.json,
        `assumed`). ``builtin:k-exaone-236b-a23b`` | ``builtin:k-exaone-test``."""
        period = (1, 1, 1, 0)
        dims = {
            # the benchmark's cut in small: a dense layer, a whole period, the module behind a global layer;
            # a window of 8 that binds on any row past 8 tokens
            "test": dict(vocab_size=259, hidden_size=64, num_layers=5, num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=128, max_position_embeddings=128,
                         moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2, sliding_window=8,
                         sliding_window_layout=(1,) + period, rope_layout=(1,) + period),
            "236b-a23b": dict(vocab_size=153600, hidden_size=6144, num_layers=48, num_heads=64, num_kv_heads=8, head_dim=128, intermediate_size=18432, max_position_embeddings=262144,
                              moe_intermediate_size=2048, num_experts=128, num_experts_per_tok=8, sliding_window=128,
                              sliding_window_layout=period * 12, rope_layout=period * 12),
        }[size]
        return _make_preset(
            dims,
            overrides,
            model_type="exaone_moe",
            position_scheme="rotary",
            rope_theta=1e6,
            norm="rmsnorm",
            layer_norm_epsilon=1e-5,
            activation="silu",
            attn_bias=False,
            mlp_bias=False,
            tie_word_embeddings=False,
            qk_norm="head",
            first_k_dense=1,
            num_shared_experts=1,
            moe_gated=True,
            moe_scoring="sigmoid",
            routed_scaling_factor=2.5,
            moe_capacity_factor=0.0,  # dropless
            moe_renormalize=True,  # norm_topk_prob: true
            router_aux_coef=0.0,  # the config publishes no balance loss
            mtp_layers=1,
            embed_init_std=1.0,
            qk_init_std=QK_INIT_STD_EXAONE,
        )

    @staticmethod
    def minicpm_sala(size: str = "9b", **overrides) -> "TransformerConfig":
        """MiniCPM-SALA 9B (``model_type`` ``minicpm_sala``): 24 of 32 layers
        are lightning linear attention (``LightningMixer``: 32 heads of 128,
        per-head QK-norm, rotary, a fixed decay a head, an RMSNorm over the
        joined heads and a sigmoid gate; the layer's whole cache is its
        float32 state), 8 (``mixer_types`` ``minicpm4``) are GQA 32/2
        attention without rotary, with a per-head QK-norm and an output gate,
        under MiniCPM4's block selection (InfLLM-V2: 64 blocks of 64 keys a
        query, chosen a KV group from mean-pooled keys; ``select_blocks``);
        SwiGLU; muP scalings (embedding x ``scale_emb``, both residual
        branches x ``scale_depth / sqrt(32)``, logits over ``hidden /
        dim_model_base``). ``mixer_layout`` says each layer's kind and the
        rotary follows it (lightning: yes, attention: no), so a cut of the
        depth overrides ``mixer_layout`` alone. Limits: the plain sampler,
        the scoring forward, the hydra branch and the train step
        (``ops/cache_layout.py::refuse``); no ``scan_layers``, no
        ring attention over ``sequence``, no HF checkpoint import.
        ``qk_init_std`` is the stand-in scale of the scores, in the per-head
        norms' scales, ``embed_init_std`` that of the token embedding, ``1 /
        scale_emb`` (chipbench/configs/minicpm-sala-9b-l8.json, `assumed`).
        ``builtin:minicpm-sala-9b`` | ``builtin:minicpm-sala-test``."""
        a, l = "attention", "lightning"
        dims = {
            # the benchmark's cut in small: a sparse layer, lightning layers, a sparse layer last; blocks of 8 keys,
            # kernels of 4 every 2, 5 blocks a query of which up to 4 are forced, dense under 32 slots; every
            # scaling other than 1
            "test": dict(vocab_size=259, hidden_size=64, num_layers=4, num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=128, max_position_embeddings=256,
                         lightning_heads=4, lightning_head_dim=16, lightning_chunk=8, mixer_layout=(a, l, l, a),
                         sparse_topk=5, sparse_block=8, sparse_kernel=4, sparse_stride=2, sparse_window=12, sparse_dense_len=32,
                         embedding_multiplier=2.0, lm_head_multiplier=0.5, residual_multiplier=0.7),
            "9b": dict(vocab_size=73448, hidden_size=4096, num_layers=32, num_heads=32, num_kv_heads=2, head_dim=128, intermediate_size=16384, max_position_embeddings=524288,
                       lightning_heads=32, lightning_head_dim=128, lightning_chunk=128,
                       mixer_layout=(a,) + (l,) * 8 + (a,) + (l,) * 6 + (a, a) + (l,) * 4 + (a,) + (l,) * 6 + (a, a, a),
                       sparse_topk=64, sparse_block=64, sparse_kernel=32, sparse_stride=16, sparse_window=2048, sparse_dense_len=8192,
                       embedding_multiplier=12.0, lm_head_multiplier=256.0 / 4096.0, residual_multiplier=1.4 / math.sqrt(32)),
        }[size]
        kinds = overrides.get("mixer_layout", dims["mixer_layout"])
        overrides.setdefault("rope_layout", tuple(int(kind == l) for kind in kinds))
        return _make_preset(
            dims,
            overrides,
            model_type="minicpm_sala",
            position_scheme="rotary",
            rope_theta=10000.0,
            norm="rmsnorm",
            layer_norm_epsilon=1e-6,
            activation="silu",
            attn_bias=False,
            mlp_bias=False,
            tie_word_embeddings=False,
            qk_norm="head",
            attn_output_gate=True,
            embed_init_std=EMBED_INIT_STD_MINICPM_SALA,
            qk_init_std=QK_INIT_STD_MINICPM_SALA,
        )

    @staticmethod
    def kimi_linear(size: str = "48b-a3b", **overrides) -> "TransformerConfig":
        """Kimi-Linear-48B-A3B (``model_type`` ``kimi_linear``): 20 of 27
        layers are Kimi Delta Attention (``KDAMixer``: 32 heads of 128 behind
        causal depthwise convs of width 4, L2-normed q and k, a gated delta
        rule whose decay is a vector a head, a per-head output norm under a
        sigmoid gate; the layer's whole cache is its float32 state and the
        convs' last rows), 7 (every fourth, and the last) latent attention
        with NO query latent and NO rotary embedding (``LatentAttention``
        under ``q_lora_rank`` 0 and ``LayerLayout.rotary`` False: the 64
        "rope" dims are one un-rotated key the heads share); one leading dense
        SwiGLU layer, then layers of 256 routed SwiGLU experts (sigmoid
        scores, the eight largest of ``score + bias``, renormalised, times
        2.446) beside one shared expert. ``mixer_layout`` says each layer's
        kind, so a cut of the depth overrides ``num_layers`` alone. Limits:
        the plain sampler, the scoring forward, the hydra branch and the train
        step (``ops/cache_layout.py::refuse``); no ``scan_layers``, no ring attention over
        ``sequence``, no HF checkpoint import. ``qk_init_std`` is the stand-in
        scale of the latent layers' ``q_proj``
        (chipbench/configs/kimi-linear-48b-a3b-l8e32.json, `assumed`).
        ``builtin:kimi-linear-48b-a3b`` | ``builtin:kimi-linear-test``."""
        a, k = "attention", "kda"
        dims = {
            # the benchmark's cut in small: a dense KDA layer, two KDA expert layers, a latent expert layer; unlike
            # sizes everywhere a test can tell them apart (q/k 20 = 12 + 8, v 16, KDA heads of 24), 8 experts
            "test": dict(vocab_size=259, hidden_size=64, num_layers=4, num_heads=4, intermediate_size=128, max_position_embeddings=256,
                         kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16,
                         kda_heads=2, kda_head_dim=24, kda_conv=4, mixer_layout=(k, k, k, a),
                         moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2, first_k_dense=1, moe_bias_init_std=0.05),
            "48b-a3b": dict(vocab_size=163840, hidden_size=2304, num_layers=27, num_heads=32, num_kv_heads=32, intermediate_size=9216, max_position_embeddings=1048576,
                            kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                            kda_heads=32, kda_head_dim=128, kda_conv=4, mixer_layout=(k, k, k, a) * 6 + (k, k, a),
                            moe_intermediate_size=1024, num_experts=256, num_experts_per_tok=8, first_k_dense=1),
        }[size]
        overrides.setdefault("rope_layout", (0,) * len(overrides.get("mixer_layout", dims["mixer_layout"])))  # mla_use_nope
        return _make_preset(
            dims,
            overrides,
            model_type="kimi_linear",
            position_scheme="rotary",  # (what `LatentAttention` is built under; no layer applies one: rope_layout)
            rope_theta=10000.0,
            norm="rmsnorm",
            layer_norm_epsilon=1e-5,
            activation="silu",
            attn_bias=False,
            mlp_bias=False,
            tie_word_embeddings=False,
            num_shared_experts=1,
            moe_gated=True,
            moe_scoring="sigmoid",
            moe_topk_method="noaux_tc",
            routed_scaling_factor=2.446,
            moe_capacity_factor=0.0,  # dropless
            moe_renormalize=True,
            router_aux_coef=0.0,  # balance is the selection bias's work, not a loss's
            embed_init_std=1.0,
            qk_init_std=QK_INIT_STD_KIMI_LINEAR,
        )

    @staticmethod
    def lfm2(size: str = "8b-a1b", **overrides) -> "TransformerConfig":
        """LFM2-8B-A1B (``model_type`` ``lfm2_moe``): 18 of 24 layers mix the
        sequence by a gated short convolution alone (``ShortConvMixer``:
        ``in_proj`` to ``B | C | x``, ``C * conv3(B * x)``, ``out_proj``; no
        activation, no bias; the conv's last two input rows are the layer's
        whole cache), 6 (``layer_types`` ``full_attention``) by GQA 32/8
        attention at a head of 64 with an RMSNorm on each head's q and k
        (``qk_norm: "head"``) and rotary embedding at theta 1e6; two leading
        dense SwiGLU layers of 7168, then layers of 32 routed SwiGLU experts
        of 1792 (sigmoid scores, the four largest of ``score + bias``,
        renormalised over ``sum + 1e-6``, times 1), no shared expert; the head
        tied to the embedding. ``mixer_layout`` says each layer's kind, so a
        cut of the depth overrides ``num_layers`` alone. Limits: the plain
        sampler, the scoring forward, the hydra branch and the train step
        (``ops/cache_layout.py::refuse``); no ``scan_layers``, no ring attention
        over ``sequence``, no HF checkpoint import. ``qk_init_std`` is the
        stand-in scale of the scores, in the per-head norms' scales
        (chipbench/configs/lfm2-8b-a1b-l10e8.json, `assumed`).
        ``builtin:lfm2-8b-a1b`` | ``builtin:lfm2-test``."""
        a, c = "attention", "conv"
        dims = {
            # the benchmark's cut in small: two dense conv layers, an attention layer, conv layers, an attention
            # layer, a conv layer last; GQA 4/2 of 16, 8 experts of 32 top-2 under a bias that binds
            "test": dict(vocab_size=259, hidden_size=64, num_layers=6, num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=128, max_position_embeddings=256,
                         mixer_layout=(c, c, a, c, a, c), moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2, moe_bias_init_std=0.05),
            "8b-a1b": dict(vocab_size=65536, hidden_size=2048, num_layers=24, num_heads=32, num_kv_heads=8, head_dim=64, intermediate_size=7168, max_position_embeddings=128000,
                           mixer_layout=(c, c, a, c, c, c, a, c, c, c, a, c, c, c, a, c, c, c, a, c, c, a, c, c),
                           moe_intermediate_size=1792, num_experts=32, num_experts_per_tok=4),
        }[size]
        return _make_preset(
            dims,
            overrides,
            model_type="lfm2_moe",
            position_scheme="rotary",
            rope_theta=1e6,
            norm="rmsnorm",
            layer_norm_epsilon=1e-5,
            activation="silu",
            attn_bias=False,
            mlp_bias=False,
            tie_word_embeddings=True,
            qk_norm="head",
            first_k_dense=2,  # num_dense_layers
            moe_gated=True,
            moe_scoring="sigmoid",
            moe_topk_method="noaux_tc",  # use_expert_bias
            routed_scaling_factor=1.0,
            moe_capacity_factor=0.0,  # dropless
            moe_renormalize=True,  # norm_topk_prob: true
            moe_renormalize_eps=1e-6,
            router_aux_coef=0.0,  # balance is the selection bias's work, not a loss's
            # (the embedding keeps the program's 0.02: tied, it is the head too, and at the 1.0 of the other
            # expert stand-ins a token's own logit is its embedding's squared norm, 2048 against a spread of 45:
            # every rollout repeats its prompt's last token)
            qk_init_std=QK_INIT_STD_LFM2,
        )

    @staticmethod
    def nemotron_h(size: str = "nano-30b-a3b", **overrides) -> "TransformerConfig":
        """NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type`` ``nemotron_h``): 52
        layers of ONE sublayer each, ``h <- h + f_i(norm_i(h))``, the kind said
        by ``hybrid_override_pattern[i]``: ``M`` a Mamba-2 mixer alone (64
        heads of 64 in 8 groups, state 128, conv 4; its float32 state and its
        conv's last rows are the layer's whole cache), ``*`` GQA 32/2 attention
        at a head of 128 with NO rotary embedding and no other positional term,
        ``E`` 128 routed experts of two matrices and ``relu(x)^2`` (sigmoid
        scores, the six largest of ``score + bias``, renormalised, times 2.5)
        beside one shared expert of its own published width; RMSNorm, no bias
        on a projection, no multiplier, the head untied. The published string
        is kept as a field and both layouts are derived from it
        (``__post_init__``), so a cut of the depth overrides ``num_layers``
        alone. Limits: the plain sampler, the scoring forward, the hydra branch
        and the train step (``ops/cache_layout.py::refuse``); no
        ``scan_layers``, no HF checkpoint import. ``mamba_in_proj_init_std``,
        ``qk_init_std``, ``embed_init_std`` are stand-in scales
        (chipbench/configs/nemotron3-nano-30b-a3b-l9e8.json, `assumed`).
        ``builtin:nemotron3-nano-30b-a3b`` | ``builtin:nemotron-h-test``."""
        dims = {
            # the benchmark's cut in small: its nine letters, all three kinds; Mamba heads 4 x 16 in 2 groups, state
            # 32, chunk 8; GQA 4/2 of 16; 8 experts of 32 top-2 under a bias that binds, a shared one of twice that
            "test": dict(vocab_size=259, hidden_size=64, num_layers=9, num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=32, max_position_embeddings=256,
                         hybrid_override_pattern="MEMEM*EME", mamba_heads=4, mamba_head_dim=16, mamba_groups=2, mamba_state=32, mamba_chunk=8,
                         moe_intermediate_size=32, moe_shared_expert_intermediate_size=64, num_experts=8, num_experts_per_tok=2, moe_bias_init_std=0.05),
            "nano-30b-a3b": dict(vocab_size=131072, hidden_size=2688, num_layers=52, num_heads=32, num_kv_heads=2, head_dim=128, intermediate_size=1856,
                                 max_position_embeddings=262144, hybrid_override_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
                                 mamba_heads=64, mamba_head_dim=64, mamba_groups=8, mamba_state=128, mamba_chunk=128,
                                 moe_intermediate_size=1856, moe_shared_expert_intermediate_size=3712, num_experts=128, num_experts_per_tok=6),
        }[size]
        return _make_preset(
            dims,
            overrides,
            model_type="nemotron_h",
            position_scheme="none",  # the attention layers apply no positional term: the state-space layers carry order
            rope_theta=10000.0,  # published, and read by nothing
            mamba_conv=4,
            norm="rmsnorm",
            layer_norm_epsilon=1e-5,
            activation="relu2",  # mlp_hidden_act: the routed and the shared experts alike
            attn_bias=False,
            mlp_bias=False,
            tie_word_embeddings=False,
            num_shared_experts=1,
            moe_gated=False,  # two matrices an expert
            moe_scoring="sigmoid",
            moe_topk_method="noaux_tc",
            routed_scaling_factor=2.5,
            moe_capacity_factor=0.0,  # dropless
            moe_renormalize=True,  # norm_topk_prob
            router_aux_coef=0.0,  # balance is the selection bias's work, not a loss's
            # two stand-in scales that keep the held share of the assignments steady over seeds, which a trained
            # selection bias does by balancing the load (PERF.md section 6, PR 63): the stream a router reads is the
            # token's own embedding before it is anything else (at 1.0 the sublayers' constant parts, a Mamba-2
            # output's and `relu^2`'s, decide which experts are popular and 8 held of 128 see 2.7 to 8.1% of the
            # assignments by the seed) ...
            embed_init_std=32.0,
            # ... and in_proj's z, x, B, C pre-activations at an RMS of 1 on a normed input (Falcon-H1's 0.5 would put
            # them at 26 and saturate every unit; at 3 `silu` is a `relu`, x, B and C are all positive and a head's
            # output is one constant vector under a token's gate)
            mamba_in_proj_init_std=1.0 / float(np.sqrt(overrides.get("hidden_size", dims["hidden_size"]))),
            qk_init_std=QK_INIT_STD_NEMOTRON_H,
        )

    @staticmethod
    def dots3(size: str = "note", **overrides) -> "TransformerConfig":
        """dots3-note-prev (``model_type`` ``dots3_note``): latent attention
        of TWO geometries in one stack (``attention_sizes``). A full layer
        (13 of 46) runs 128 heads over a 512-wide latent under a learned
        selection of ``index_topk`` keys that its own indexer makes; a window
        layer (three in four, ``sliding_window_layout``) runs the ``swa_*``
        sizes, 64 heads over a 1024-wide latent inside a window of 513, selects
        nothing, and caches a ring of 513 latents. Both under a sigmoid gate a
        head (``attention_gate_type`` headwise) and with the normed latents at
        ``sqrt(hidden / rank)`` (``mla_lora_rescale``); one leading dense SwiGLU
        layer, then layers of 256 routed SwiGLU experts (sigmoid scores, the
        eight largest of ``score + bias``, renormalised, times 1) beside one
        shared expert. The vision and audio towers and the next-token module
        are not built. Limits: the plain sampler, the scoring forward, the
        hydra branch and the train step only (``ops/cache_layout.py::refuse``); no ``scan_layers``, no ring attention over
        ``sequence``, no HF checkpoint import. ``q_b_proj`` keeps the program's
        0.02: under the rescale that is already a score of standard deviation 2
        (chipbench/configs/dots3-note-prev-l6e8.json, `assumed`).
        ``builtin:dots3-note`` | ``builtin:dots3-note-test``."""
        f, w = 0, 1  # sliding_window_layout: a full layer, a window layer
        dims = {
            # the benchmark's cut in small (dense full, full, window x 3, full), the two kinds unlike in EVERY size so
            # that a test can tell a swapped one: full 4 heads, latents 32 / 16, q/k 20 = 12 + 8, v 16; window 2 heads,
            # latents 24 / 24, q/k 24 = 20 + 4, v 12; a window of 5 and a selection of 8 that bind on any row past 8
            "note-test": dict(vocab_size=259, hidden_size=64, num_layers=6, num_heads=4, intermediate_size=128, max_position_embeddings=256,
                              q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16, rope_theta=8e7,
                              swa_num_heads=2, swa_q_lora_rank=24, swa_kv_lora_rank=24, swa_qk_nope_head_dim=20, swa_qk_rope_head_dim=4,
                              swa_v_head_dim=12, swa_rope_theta=5e4, sliding_window=5, sliding_window_layout=(f, f, w, w, w, f),
                              index_topk=8, index_heads=2, index_head_dim=12,
                              moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2, first_k_dense=1, moe_bias_init_std=0.05),
            "note": dict(vocab_size=152064, hidden_size=5120, num_layers=46, num_heads=128, num_kv_heads=128, intermediate_size=13824, max_position_embeddings=524288,
                         q_lora_rank=1024, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, rope_theta=8e7,
                         swa_num_heads=64, swa_q_lora_rank=1024, swa_kv_lora_rank=1024, swa_qk_nope_head_dim=192, swa_qk_rope_head_dim=64,
                         swa_v_head_dim=128, swa_rope_theta=5e4, sliding_window=513, sliding_window_layout=(f, f) + (w, w, w, f) * 11,
                         index_topk=2048, index_heads=64, index_head_dim=128,
                         moe_intermediate_size=1536, num_experts=256, num_experts_per_tok=8, first_k_dense=1),
        }[size]
        return _make_preset(
            dims,
            overrides,
            model_type="dots3_note",
            position_scheme="rotary",
            norm="rmsnorm",
            layer_norm_epsilon=1e-5,
            activation="silu",
            attn_bias=False,
            mlp_bias=False,
            tie_word_embeddings=False,
            attention_gate_type="headwise",
            mla_lora_rescale=True,
            num_shared_experts=1,
            moe_gated=True,
            moe_scoring="sigmoid",
            moe_topk_method="noaux_tc",
            routed_scaling_factor=1.0,
            moe_capacity_factor=0.0,  # dropless
            moe_renormalize=True,  # norm_topk_prob: true
            router_aux_coef=0.0,  # balance is the selection bias's work, not a loss's
            embed_init_std=1.0,
        )

    @staticmethod
    def falconh1(size: str = "34b", **overrides) -> "TransformerConfig":
        """Falcon-H1: Mamba-2 heads beside attention heads in every block.
        Limits: the plain sampler, the scoring forward and the train step
        only (``ops/cache_layout.py::refuse``); no HF checkpoint import."""
        dims = {
            # every multiplier differs from 1, so that a test sees each
            "test": dict(vocab_size=259, hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=128, max_position_embeddings=128,
                         mamba_heads=4, mamba_head_dim=16, mamba_groups=2, mamba_state=32, mamba_chunk=8,
                         embedding_multiplier=2.0, lm_head_multiplier=0.5, attention_in_multiplier=0.75, attention_out_multiplier=0.6, key_multiplier=0.5,
                         mlp_multipliers=(0.7, 0.4), ssm_in_multiplier=0.8, ssm_out_multiplier=0.7, ssm_multipliers=(0.9, 0.8, 0.7, 1.3, 1.1)),
            "34b": dict(vocab_size=261120, hidden_size=5120, num_layers=72, num_heads=20, num_kv_heads=4, head_dim=128, intermediate_size=21504, max_position_embeddings=262144,
                        mamba_heads=32, mamba_head_dim=128, mamba_groups=2, mamba_state=256, mamba_chunk=128,
                        embedding_multiplier=5.656854249492381, lm_head_multiplier=0.0078125, attention_in_multiplier=1.0, attention_out_multiplier=0.0375, key_multiplier=0.011048543456039804,
                        mlp_multipliers=(0.1767766952966369, 0.011160714285714284), ssm_in_multiplier=0.25, ssm_out_multiplier=0.08838834764831845,
                        ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738)),
        }[size]
        return _make_preset(
            dims,
            overrides,
            model_type="falcon_h1",
            mixer="mamba2",
            mamba_conv=4,
            position_scheme="rotary",
            rope_theta=1e11,
            norm="rmsnorm",
            layer_norm_epsilon=1e-5,
            activation="silu",
            attn_bias=False,
            mlp_bias=False,
            tie_word_embeddings=False,
        )

    @staticmethod
    def gptj(size: str = "6b", **overrides) -> "TransformerConfig":
        dims = {
            "test": dict(vocab_size=259, hidden_size=64, num_layers=2, num_heads=4, intermediate_size=256, max_position_embeddings=128),
            "6b": dict(vocab_size=50400, hidden_size=4096, num_layers=28, num_heads=16, intermediate_size=16384, max_position_embeddings=2048),
        }[size]
        return _make_preset(
            dims,
            overrides,
            model_type="gptj",
            position_scheme="rotary",
            rotary_dim=64 if size != "test" else 8,
            norm="layernorm",
            activation="gelu_new",
            parallel_residual=True,
            shared_ln=True,
            attn_bias=False,
            qkv_bias=False,
            mlp_bias=True,
            tie_word_embeddings=False,
            lm_head_bias=True,
        )

    @staticmethod
    def gptneox(size: str = "160m", **overrides) -> "TransformerConfig":
        dims = {
            "test": dict(vocab_size=259, hidden_size=64, num_layers=2, num_heads=4, intermediate_size=256, max_position_embeddings=128),
            "160m": dict(vocab_size=50304, hidden_size=768, num_layers=12, num_heads=12, intermediate_size=3072, max_position_embeddings=2048),
            "1.4b": dict(vocab_size=50304, hidden_size=2048, num_layers=24, num_heads=16, intermediate_size=8192, max_position_embeddings=2048),
            "6.9b": dict(vocab_size=50432, hidden_size=4096, num_layers=32, num_heads=32, intermediate_size=16384, max_position_embeddings=2048),
            "20b": dict(vocab_size=50432, hidden_size=6144, num_layers=44, num_heads=64, intermediate_size=24576, max_position_embeddings=2048),
        }[size]
        return _make_preset(
            dims,
            overrides,
            model_type="gpt_neox",
            position_scheme="rotary",
            rotary_dim=(dims["hidden_size"] // dims["num_heads"]) // 4 if size != "test" else 4,
            norm="layernorm",
            activation="gelu",
            parallel_residual=True,
            shared_ln=False,
            attn_bias=True,
            mlp_bias=True,
            tie_word_embeddings=False,
        )

    @staticmethod
    def opt(size: str = "125m", **overrides) -> "TransformerConfig":
        dims = {
            "test": dict(vocab_size=259, hidden_size=64, num_layers=2, num_heads=4, intermediate_size=256, max_position_embeddings=128),
            "125m": dict(vocab_size=50272, hidden_size=768, num_layers=12, num_heads=12, intermediate_size=3072, max_position_embeddings=2048),
            "6.7b": dict(vocab_size=50272, hidden_size=4096, num_layers=32, num_heads=32, intermediate_size=16384, max_position_embeddings=2048),
        }[size]
        return _make_preset(
            dims,
            overrides,
            model_type="opt",
            position_scheme="learned",
            pos_offset=2,
            norm="layernorm",
            activation="relu",
            tie_word_embeddings=True,
        )

    @staticmethod
    def bloom(size: str = "560m", **overrides) -> "TransformerConfig":
        dims = {
            "test": dict(vocab_size=259, hidden_size=64, num_layers=2, num_heads=4, intermediate_size=256, max_position_embeddings=128),
            "560m": dict(vocab_size=250880, hidden_size=1024, num_layers=24, num_heads=16, intermediate_size=4096, max_position_embeddings=2048),
        }[size]
        return _make_preset(
            dims,
            overrides,
            model_type="bloom",
            position_scheme="alibi",
            norm="layernorm",
            activation="gelu",
            embedding_layernorm=True,
            tie_word_embeddings=True,
        )



def _make_preset(dims: dict, overrides: dict, **flags) -> "TransformerConfig":
    """Build a preset config: dims + family flags, with ``overrides`` able to
    replace ANY field (dimension or architecture flag) without conflicts."""
    base = {**dims, **flags}
    base.update(overrides)
    return TransformerConfig(**base)

def get_activation(name: str) -> Callable:
    return {
        "gelu_new": partial(nn.gelu, approximate=True),
        "gelu": partial(nn.gelu, approximate=False),
        "silu": nn.silu,
        "relu": nn.relu,
        "relu2": lambda x: jnp.square(nn.relu(x)),
    }[name]


# ---------------------------------------------------------------------------
# Positional encodings
# ---------------------------------------------------------------------------


def rotary_sin_cos(positions: jax.Array, dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    """sin/cos tables for RoPE at integer ``positions`` [B, T] → [B, T, dim/2]."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    freqs = positions[..., None].astype(jnp.float32) * inv_freq  # [B, T, dim/2]
    return jnp.sin(freqs), jnp.cos(freqs)


def apply_rotary(x: jax.Array, sin: jax.Array, cos: jax.Array, rotary_dim: int, neox_style: bool) -> jax.Array:
    """Apply RoPE to the first ``rotary_dim`` dims of x [B, T, H, D].

    ``neox_style=True`` rotates split halves (llama/neox); False rotates
    interleaved even/odd pairs (gptj).
    """
    x_rot, x_pass = x[..., :rotary_dim], x[..., rotary_dim:]
    sin = sin[:, :, None, :]  # [B, T, 1, dim/2]
    cos = cos[:, :, None, :]
    if neox_style:
        half = rotary_dim // 2
        x1, x2 = x_rot[..., :half], x_rot[..., half:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    else:
        x1, x2 = x_rot[..., ::2], x_rot[..., 1::2]
        r1 = x1 * cos - x2 * sin
        r2 = x2 * cos + x1 * sin
        out = jnp.stack([r1, r2], axis=-1).reshape(x_rot.shape)
    return jnp.concatenate([out, x_pass], axis=-1).astype(x.dtype)


def alibi_slopes(num_heads: int) -> np.ndarray:
    """ALiBi per-head slopes (Press et al.), matching the BLOOM recipe."""

    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(np.log2(n) - 3)))
        return start * (start ** np.arange(n))

    if np.log2(num_heads).is_integer():
        return pow2_slopes(num_heads)
    closest = 2 ** int(np.floor(np.log2(num_heads)))
    base = pow2_slopes(closest)
    extra = pow2_slopes(2 * closest)[0::2][: num_heads - closest]
    return np.concatenate([base, extra])


# ---------------------------------------------------------------------------
# Modules
# ---------------------------------------------------------------------------


def Norm(config: TransformerConfig, name: str):
    """LayerNorm/RMSNorm with params directly at ``<name>/{scale,bias}``."""
    cls = nn.RMSNorm if config.norm == "rmsnorm" else nn.LayerNorm
    kwargs = {}
    if config.norm != "rmsnorm":
        kwargs["bias_init"] = param_with_axes(nn.initializers.zeros, ("embed",))
    return cls(
        epsilon=config.layer_norm_epsilon,
        dtype=config.dtype,
        param_dtype=config.param_dtype,
        scale_init=param_with_axes(nn.initializers.ones, ("embed",)),
        name=name,
        **kwargs,
    )


def _qk_norm(config: TransformerConfig, name: str):
    # a per-head norm divides q_proj's and k_proj's scale out again, so under it the
    # stand-in weights' `qk_init_std` goes into the norms' learned scales instead
    # (1 at the default 0.02): the scores' spread is the product of the two scales
    scale = config.qk_init_std / 0.02 if config.qk_norm == "head" else 1.0
    return nn.RMSNorm(
        epsilon=config.layer_norm_epsilon,
        dtype=config.dtype,
        param_dtype=config.param_dtype,
        scale_init=param_with_axes(nn.initializers.constant(scale), ("joined_kv",)),
        name=name,
    )


class LoRADense(nn.Module):
    """Dense with an additive low-rank branch: ``y = xW (+b) + (alpha/r)·xAB``.

    Parameters live at the same tree level as a plain Dense (``kernel``/
    ``bias`` plus ``lora_a``/``lora_b``), so HF import and the path-based
    sharding rules are unchanged. ``lora_b`` is zero-init: the module is an
    exact no-op until trained."""

    features: int
    use_bias: bool
    dtype: Any
    param_dtype: Any
    kernel_init: Callable
    bias_init: Callable
    r: int
    alpha: float

    @nn.compact
    def __call__(self, x):
        in_features = x.shape[-1]
        kernel = self.param("kernel", self.kernel_init, (in_features, self.features), self.param_dtype)
        y = x @ kernel.astype(self.dtype)
        if self.use_bias:
            bias = self.param("bias", self.bias_init, (self.features,), self.param_dtype)
            y = y + bias.astype(self.dtype)
        a = self.param("lora_a", nn.initializers.he_uniform(), (in_features, self.r), self.param_dtype)
        b = self.param("lora_b", nn.initializers.zeros, (self.r, self.features), self.param_dtype)
        scale = self.alpha / self.r
        y = y + (x @ a.astype(self.dtype)) @ b.astype(self.dtype) * scale
        return y


def _dense(cfg, features, use_bias, kernel_axes, name=None, std=0.02):
    kernel_init = param_with_axes(nn.initializers.normal(std), kernel_axes)
    bias_init = param_with_axes(nn.initializers.zeros, (kernel_axes[-1],))
    if getattr(cfg, "lora_r", 0) and name in getattr(cfg, "lora_targets", ()):
        return LoRADense(
            features,
            use_bias,
            cfg.dtype,
            cfg.param_dtype,
            kernel_init,
            bias_init,
            cfg.lora_r,
            cfg.lora_alpha,
            name=name,
        )
    return nn.Dense(
        features,
        use_bias=use_bias,
        dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
        kernel_init=kernel_init,
        bias_init=bias_init,
        name=name,
    )


def grouped_einsum_attention(q, k, v, attention_bias, dtype) -> jax.Array:
    """Dense attention ``[B, T, H, D]`` of ``q [B, T, H, D]`` over unrepeated
    ``k``, ``v [B, S, KV, D]`` under an additive ``attention_bias``
    ``[B, 1 | H, T, S]``, softmax in float32.

    Query heads ``j*G .. j*G+G-1`` (``G = H // KV``) share KV head ``j``, so
    the head axis is viewed as ``[KV, G]`` and both contractions run against
    K and V as the cache holds them: nothing of ``B*S*H*D`` elements is ever
    built. MHA is ``G = 1``.

    **A lane-packed cache** (``k``, ``v [B, S, KV / P, P * D]``, ``P`` heads
    side by side in a row: ``ops/cache_layout.py::lane_heads``) is read as it
    lies too, ``P`` taken from the leaf: each query head's ``D`` channels
    stand in lanes ``[w * D, (w + 1) * D)`` of a zero row of ``P * D`` (``w``
    its KV head's place in the row), so a packed head has ``P * G`` query
    rows and both contractions run over the packed axis; of a result row's
    ``P * D`` lanes its own head's ``D`` are kept. A zero times a finite key
    adds an exact zero to a float32 sum and the value product's lanes do not
    mix, so the result is the unpacked one's; the scale stays ``1 / sqrt(D)``.
    No ``[.., KV, D]`` view of the leaf is taken: inside a decode loop that
    view is what turns the carried cache slot-minor at a head under 128."""
    B, T, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    P = k.shape[3] // D
    G = H // (KV * P)
    rows = q.reshape(B, T, KV, P * G, D)
    if P > 1:
        own = jnp.eye(P, dtype=bool)[:, None, :, None]  # [w, 1, lanes' w, 1]: a query row's own head's lanes
        rows = jnp.where(own, q.reshape(B, T, KV, P, G, 1, D), 0).reshape(B, T, KV, P * G, P * D)
    scores = jnp.einsum("btkgd,bskd->bkgts", rows, k).reshape(B, H, T, S)
    scores = scores / jnp.sqrt(jnp.asarray(D, dtype))
    scores = scores + attention_bias.astype(scores.dtype)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", probs.reshape(B, KV, P * G, T, S), v)
    if P > 1:
        out = out.reshape(B, T, KV, P, G, P, D)
        out = jnp.stack([out[:, :, :, w, :, w] for w in range(P)], axis=3)
    return out.reshape(B, T, H, v.shape[-1] // P)


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class StaticExtents:
    """What ``Attention`` is told, statically, of the cache it is handed: the
    sampler's ``kv_extents`` cut to this layer's cache, and whether that cache
    is a ring (a window layer's, shorter than the row: slot ``t`` lives at
    ``t mod slots[-1]``). A pytree with no leaves, so ``nn.remat``,
    ``nn.scan`` and the pipeline's ``jax.checkpoint`` hand the Python values
    through instead of tracing them."""

    slots: Tuple[int, ...]
    ring: bool = False


def extent_attention(q, k, v, attention_bias, cache_index, kv_extents, dtype) -> jax.Array:
    """``grouped_einsum_attention`` of one query token over the shortest
    static prefix of the cache that holds the slot just written.

    ``kv_extents`` is an ascending tuple of slot counts ending at the cache's
    own (``ops/sampling.py::kv_extents``); a step writing slot
    ``cache_index`` sees slots ``[0, cache_index]``, so it takes the first
    extent of at least ``cache_index + 1``. Every slot above the one written
    is masked by the bias and adds ``exp(-1e9) = 0.0`` to the softmax, so
    leaving it unread changes no term, only how many bytes of K and V the
    step moves. Each branch slices its own operands: q, the whole caches and
    the bias go into the conditional as they are, and nothing is copied."""

    def over(extent):
        def attend(q, k, v, bias):
            return grouped_einsum_attention(
                q, k[:, :extent], v[:, :extent], bias[..., :extent], dtype
            )

        return attend

    return _switch_on_extent(cache_index, kv_extents, over, q, k, v, attention_bias)


def _switch_on_extent(cache_index, kv_extents, over, *operands):
    """``over(extent)(*operands)`` for the first extent of at least
    ``cache_index + 1`` slots."""
    branch = sum((cache_index + 1 > e).astype(jnp.int32) for e in kv_extents[:-1])
    return jax.lax.switch(branch, [over(e) for e in kv_extents], *operands)


def absorbed_latent_attention(q_c, q_r, ckv, k_rope, attention_bias, cache_index, kv_extents, scale, dtype) -> jax.Array:
    """One query token over the latent cache itself, ``[B, H, r]``: scores
    ``q_c . c + q_r . k_r`` of ``q_c [B, H, r]`` (the no-rope query folded
    through the key half of ``kv_b_proj``) and ``q_r [B, H, dr]`` over ``ckv
    [B, S, r]`` and the ONE roped key ``k_rope [B, S, dr]`` every head
    shares, softmax in float32, then ``sum p c``: the caller folds the value
    half of ``kv_b_proj`` into the result. Nothing of ``B*S*H`` per-head keys
    or values is built. Over the shortest of ``kv_extents`` that holds the
    slot just written, as ``extent_attention``; ``None`` reads every slot."""

    def over(extent):
        def attend(q_c, q_r, ckv, k_rope, bias):
            c, kr, b = ckv, k_rope, bias[:, :, 0, :]
            if extent is not None:
                c, kr, b = c[:, :extent], kr[:, :extent], b[..., :extent]
            # float32 out of the products: a bf16 score of magnitude 8 to 16
            # is 2^-4 to 2^-3 apart, which a sharp softmax turns into percents
            # of a key's weight; the flash kernel of the expanded form keeps
            # its scores in float32 too
            f32 = dict(preferred_element_type=jnp.float32)
            scores = jnp.einsum("bhr,bsr->bhs", q_c, c, **f32) + jnp.einsum("bhd,bsd->bhs", q_r, kr, **f32)
            probs = jax.nn.softmax(scores * scale + b.astype(jnp.float32), axis=-1).astype(dtype)
            return jnp.einsum("bhs,bsr->bhr", probs, c)

        return attend

    with jax.named_scope("trlx/attn_latent_absorbed"):
        if kv_extents is None or len(kv_extents) < 2:
            return over(None)(q_c, q_r, ckv, k_rope, attention_bias)
        return _switch_on_extent(cache_index, kv_extents, over, q_c, q_r, ckv, k_rope, attention_bias)


class Attention(nn.Module):
    """Multi-head / grouped-query attention with RoPE/ALiBi and an explicit
    KV cache ({"k","v"} arrays [B, S, kvH, D] written at ``cache_index``).

    **Under a block selection** (``cfg.sparse_topk`` > 0, on a row of
    ``sparse_dense_len`` slots or more: the cache's slots, or a pass's
    tokens) a query's softmax runs over the keys of ``sparse_topk`` blocks of
    ``sparse_block`` keys, one choice for the query heads of each KV head
    (``select_blocks``), made from the keys' mean-pooled ``kbar``, a third
    cache leaf ``[B, KV, S / stride, D]`` that fills as kernels complete.
    Kernels and blocks are laid from each row's FIRST REAL TOKEN: a long pass
    (and the sampler's prefill, which must start at slot 0 and attends over
    its own keys) rolls its rows there and back (``roll_rows``) and runs the
    flash kernels under one more mask a tile, 64 keys to a bit
    (``block_sparse_attention``); a single-token step scores the kernels of
    the cache, completes at most one more from the last ``sparse_kernel``
    keys, and masks the dense read of the cache to the chosen blocks'
    slots. What is computed is exactly the chosen set; nothing is skipped."""

    config: TransformerConfig
    rotary: Optional[bool] = None  # the layer's layout says; None = position_scheme does

    @nn.compact
    def __call__(
        self,
        x: jax.Array,  # [B, T, E]
        attention_bias: Optional[jax.Array],  # [B, 1, T, S] additive (xla path)
        positions: jax.Array,  # [B, T]
        cache: Optional[Dict[str, jax.Array]] = None,
        cache_index: Optional[jax.Array] = None,
        flash_args: Optional[Dict[str, Any]] = None,  # pallas path (see below)
        kv_extents: Optional[StaticExtents] = None,  # see extent_attention
    ):
        cfg = self.config
        B, T, _ = x.shape
        H, KV, D = cfg.num_heads, cfg.kv_heads, cfg.dims_per_head
        qkv_bias = cfg.attn_bias if cfg.qkv_bias is None else cfg.qkv_bias

        q = _dense(cfg, H * D, qkv_bias, ("embed", "joined_kv"), "q_proj", cfg.qk_init_std)(x)
        k = _dense(cfg, KV * D, qkv_bias, ("embed", "joined_kv"), "k_proj", cfg.qk_init_std)(x)
        v = _dense(cfg, KV * D, qkv_bias, ("embed", "joined_kv"), "v_proj")(x).reshape(B, T, KV, D)
        gated = lambda out: out  # noqa: E731
        if cfg.attn_output_gate:
            gate = jax.nn.sigmoid(_dense(cfg, H * D, False, ("embed", "joined_kv"), "z_proj")(x))
            gated = lambda out: out * gate  # noqa: E731
        if cfg.key_multiplier != 1.0:
            k = k * cfg.key_multiplier
        if cfg.qk_norm is True:
            # over the whole projected width (all heads together), float32
            # statistics; every cache and kernel path below sees normed q, k
            q, k = _qk_norm(cfg, "q_norm")(q), _qk_norm(cfg, "k_norm")(k)
        q, k = q.reshape(B, T, H, D), k.reshape(B, T, KV, D)
        if cfg.qk_norm == "head":  # over each head's D dims, one scale [D] for all heads
            q, k = _qk_norm(cfg, "q_norm")(q), _qk_norm(cfg, "k_norm")(k)

        if (cfg.position_scheme == "rotary") if self.rotary is None else self.rotary:
            rdim = cfg.rotary_dim or D
            sin, cos = rotary_sin_cos(positions, rdim, cfg.rope_theta)
            neox = cfg.norm == "rmsnorm" or not cfg.shared_ln  # llama/neox vs gptj
            q = apply_rotary(q, sin, cos, rdim, neox)
            k = apply_rotary(k, sin, cos, rdim, neox)

        paged = cache is not None and isinstance(cache, dict) and "block_table" in cache
        if paged and cfg.sparse_topk:
            raise NotImplementedError("the paged Engine holds K and V blocks and no compressed keys (ops/cache_layout.py::refuse)")
        if paged:
            # in-place paged attention (ops/paged_attention.py single-token
            # decode; ops/paged_prefill.py chunked prefill): K/V live in
            # the block pool ({"k","v"} over [NB, bs, KV, D]) and this
            # call's k/v commit straight through the per-row block table —
            # no gathered dense view exists, before or after. Drop-mode
            # writes make poisoned (out-of-range) table rows — frozen slots,
            # padding lanes — write nothing, mirroring scatter_steps'/
            # scatter_span's live-writes-only commit on the gather path.
            table = cache["block_table"]
            if cache["k"].shape[-1] != D:
                raise ValueError(
                    f"a block pool's leaves are [NB, bs, KV, D] and this one's rows are {cache['k'].shape[-1]} wide at a head of {D}: "
                    "make the pool and its rows with make_kv_cache(..., lane_packed=False) (ops/cache_layout.py)"
                )
            ci = jnp.asarray(cache_index if cache_index is not None else 0)
            blk_size = cache["k"].shape[-3]
            if T == 1:
                if ci.ndim == 0:
                    ci = jnp.broadcast_to(ci, (B,))
                blk = jnp.take_along_axis(table, (ci // blk_size)[:, None], axis=1)[:, 0]
                off = ci % blk_size
                k_pool = cache["k"].at[blk, off].set(
                    k[:, 0].astype(cache["k"].dtype), mode="drop"
                )
                v_pool = cache["v"].at[blk, off].set(
                    v[:, 0].astype(cache["v"].dtype), mode="drop"
                )
                new_cache = {"k": k_pool, "v": v_pool, "block_table": table}
                from trlx_tpu.ops.paged_attention import paged_attention_decode

                # the additive bias rows carry the full masking semantics
                # (slot-causal + key validity + window/ALiBi) — identical to
                # what the dense einsum path would consume. The head dim is 1
                # (mask-only) or H (per-head ALiBi slopes) and is preserved.
                out = paged_attention_decode(
                    q[:, 0], k_pool, v_pool, table, attention_bias[:, :, 0, :]
                ).reshape(B, 1, H * D)
            else:
                # multi-position span. Two callers land here:
                #   * prefill chunk — all rows share one static span
                #     [ci, ci+T) (the refill/chunk programs group rows per
                #     span), so ci is a scalar and the commit columns are a
                #     [T] vector broadcast over rows;
                #   * speculative verify — the target scores gamma+1 probe
                #     positions per row at per-row depths (rows rewind to
                #     different accepted lengths), so ci is a [B] vector and
                #     each row writes its own [T] column window.
                # Either way every row writes through its own table's
                # blocks; shared prefix blocks sit strictly below ci and
                # are only ever read.
                verify = ci.ndim != 0
                if verify:
                    cols = ci[:, None] + jnp.arange(T)[None, :]  # [B, T]
                    blk = jnp.take_along_axis(table, cols // blk_size, axis=1)
                    off = cols % blk_size
                else:
                    cols = ci + jnp.arange(T)  # [T]
                    blk = table[:, cols // blk_size]  # [B, T]
                    off = jnp.broadcast_to((cols % blk_size)[None, :], blk.shape)
                k_pool = cache["k"].at[blk, off].set(
                    k.astype(cache["k"].dtype), mode="drop"
                )
                v_pool = cache["v"].at[blk, off].set(
                    v.astype(cache["v"].dtype), mode="drop"
                )
                new_cache = {"k": k_pool, "v": v_pool, "block_table": table}
                if verify:
                    from trlx_tpu.ops.paged_attention import (
                        paged_verify_attention,
                    )

                    out = paged_verify_attention(
                        q, k_pool, v_pool, table, attention_bias
                    ).reshape(B, T, H * D)
                else:
                    from trlx_tpu.ops.paged_prefill import (
                        paged_prefill_attention,
                    )

                    out = paged_prefill_attention(
                        q, k_pool, v_pool, table, attention_bias
                    ).reshape(B, T, H * D)
            out = _dense(cfg, cfg.hidden_size, cfg.attn_bias, ("joined_kv", "embed"), "o_proj")(gated(out))
            return out, new_cache

        new_cache = None
        # KV heads side by side in a row of this cache's `k` and `v` (ops/cache_layout.py::lane_heads), read off the
        # leaf: the new rows are written in the leaf's form where they always were, the einsum reads the leaf as it
        # lies (grouped_einsum_attention) and a flash call over the cache unpacks it, one copy of the leaf a call
        side = 1 if cache is None else cache["k"].shape[-1] // D
        k_rows, v_rows = lane_pack(k, side), lane_pack(v, side)
        ring = kv_extents is not None and kv_extents.ring
        if ring:
            # a window layer's cache of C < S slots, slot t at t mod C
            # (CausalTransformer._ring_plan built the bias / flash_args to match)
            ci = jnp.asarray(cache_index)
            C = cache["k"].shape[1]
            if ci.ndim:
                # each row's span of T tokens at its own slot (speculation's verify): slot
                # ci + i of row b goes to ring position (ci[b] + i) mod C
                rows, at = jnp.arange(B)[:, None], (ci[:, None] + jnp.arange(T)[None, :]) % C
                write = lambda c, x: c.at[rows, at].set(x.astype(c.dtype), unique_indices=True)
            elif T == 1:
                write = lambda c, x: jax.lax.dynamic_update_slice(c, x.astype(c.dtype), (0, ci % C, 0, 0))
            elif T <= C:  # a prefill from slot 0 that does not wrap
                write = lambda c, x: jax.lax.dynamic_update_slice(c, x.astype(c.dtype), (0, 0, 0, 0))
            else:  # a prefill from slot 0: its last C positions stay
                write = lambda c, x: jnp.roll(x[:, T - C :].astype(c.dtype), (T - C) % C, axis=1)
            new_cache = {"k": write(cache["k"], k_rows), "v": write(cache["v"], v_rows)}
            if T == 1 or ci.ndim:
                k, v = new_cache["k"], new_cache["v"]
            # a prefill attends over its own k, v: nothing older is in the ring
        elif cache is not None:
            # decode: write this step's k/v into the cache at cache_index —
            # a scalar (all rows aligned) or a [B] vector (speculative
            # decoding: rows rewind to different accepted lengths)
            ci = jnp.asarray(cache_index)
            if ci.ndim == 0:
                k_cache = jax.lax.dynamic_update_slice(cache["k"], k_rows.astype(cache["k"].dtype), (0, ci, 0, 0))
                v_cache = jax.lax.dynamic_update_slice(cache["v"], v_rows.astype(cache["v"].dtype), (0, ci, 0, 0))
            else:
                # each row's span of T tokens at its own slot (a speculative round's verify
                # and drafts, the slot engine's dense segment): one scatter of (row, slot)
                # pairs in place in the loop's carry, as the ring branch above writes
                k_cache = write_row_spans(cache["k"], k_rows, ci)
                v_cache = write_row_spans(cache["v"], v_rows, ci)
            new_cache = {"k": k_cache, "v": v_cache}  # (and `kbar` under a block selection: below)
            if not (cfg.sparse_topk and T > 1):  # a span under the block selection attends over its own keys
                k, v = k_cache, v_cache

        ring_mesh = None
        if flash_args is not None and cache is None:
            ring_mesh = _maybe_ring_mesh(T)
        sparse_pass = False
        if cfg.sparse_topk:
            if ring_mesh is not None or (cache is not None and ci.ndim):
                raise NotImplementedError(
                    "a block selection (sparse_topk) runs whole rows or the plain sampler's steps at one scalar cache_index: "
                    "no ring attention over `sequence`, no per-row cache_index (ROADMAP.md queue 2, B8)"
                )
            # the selection binds on a row of `sparse_dense_len` slots or more: the cache's, or a pass's tokens
            selects = (T if cache is None else cache["k"].shape[1]) >= cfg.sparse_dense_len
            sparse_pass = T > 1 and (selects or cache is not None)
            if T == 1 and cache is not None:
                attention_bias, new_cache["kbar"] = self._sparse_step_bias(q, k, cache["kbar"], attention_bias, positions, ci, selects)
        if sparse_pass:
            out, kbar = self._sparse_pass(q, k, v, attention_bias, flash_args, selects)
            if cache is not None:
                new_cache["kbar"] = jax.lax.dynamic_update_slice(cache["kbar"], kbar[:, :, : cache["kbar"].shape[2]], (0, 0, 0, 0))
            out = out.reshape(B, T, H * D)
        elif ring_mesh is not None:
            # sequence-parallel exact attention: K/V chunks rotate around the
            # mesh's ``sequence`` ring with zigzag causal placement (context
            # parallelism; beyond the reference, which caps seq_length
            # instead — SURVEY.md §5). ALiBi rides the ring as true token
            # positions.
            from trlx_tpu.parallel.ring_attention import ring_flash_attention

            out = ring_flash_attention(
                q, k, v, flash_args["key_mask"], ring_mesh,
                q_positions=flash_args.get("q_positions"),
                k_positions=flash_args.get("k_positions"),
                alibi_slopes=flash_args.get("alibi_slopes"),
                window=flash_args.get("window"),
            ).reshape(B, T, H * D)
        elif flash_args is not None:
            # (the kernel reads [.., KV, D]: the prefill's call over a packed cache, outside any decode loop)
            out = _flash_attention(q, lane_unpack(k, k.shape[-1] // D), lane_unpack(v, v.shape[-1] // D), flash_args).reshape(B, T, H * D)
        elif kv_extents is not None and len(kv_extents.slots) > 1 and T == 1 and cache is not None and ci.ndim == 0:
            # the sampler's single-token step, all rows at one slot
            out = extent_attention(q, k, v, attention_bias, ci, kv_extents.slots, cfg.dtype).reshape(B, T, H * D)
        else:
            out = grouped_einsum_attention(q, k, v, attention_bias, cfg.dtype).reshape(B, T, H * D)
        out = _dense(cfg, cfg.hidden_size, cfg.attn_bias, ("joined_kv", "embed"), "o_proj")(gated(out))
        return out, new_cache

    def _sparse_pass(self, q, k, v, attention_bias, flash_args, selects):
        """Whole rows (or the sampler's prefill from slot 0) of a layer under
        the block selection: ``(out [B, T, H, D], kbar [B, KV, T / stride,
        D])``. Rows are rolled to their first real token, where kernels and
        blocks start, and the result back; a row of under ``sparse_dense_len``
        slots (``selects`` False) attends densely and still leaves its ``kbar``."""
        cfg = self.config
        B, T = q.shape[:2]
        if flash_args is not None:
            real = flash_args["key_mask"][:, :T] > 0
        else:  # the last query's row of the bias: every valid key of the span
            real = attention_bias[:, 0, -1, :T] > -1.0
        lead = jnp.argmax(real, axis=1)  # pads in front of each row
        q, k, v, real = (roll_rows(a, lead) for a in (q, k, v, real))
        kbar = pooled_keys(k * real[:, :, None, None].astype(k.dtype), cfg)

        def seen(start, tq, tk):  # [B, tq, tk]: causal by position, and no padded key
            causal = jnp.arange(tk)[None, :] <= start + jnp.arange(tq)[:, None]
            return causal[None] & real[:, None, :tk]

        def chosen_of(start, tq, tk):
            t = (start + jnp.arange(tq))[None, :]
            kernels = max((tk - cfg.sparse_kernel) // cfg.sparse_stride + 1, 1)
            rows = jax.lax.dynamic_slice_in_dim(q, start, tq, axis=1)
            return select_blocks(rows, kbar[:, :, :kernels], t, -(-tk // cfg.sparse_block), cfg)

        if selects:
            args = None if flash_args is None else {"key_mask": real.astype(jnp.int32), "q_offset": 0}
            out = block_sparse_attention(cfg, q, k, v, seen, chosen_of, cfg.dtype, args)
        elif flash_args is not None:
            out = _flash_attention(q, k, v, {"key_mask": real.astype(jnp.int32), "q_offset": 0})
        else:
            bias = jnp.where(seen(0, T, T), 0.0, -1e9)[:, None]
            out = grouped_einsum_attention(q, k, v, bias, cfg.dtype)
        return roll_rows(out, -lead), kbar

    def _sparse_step_bias(self, q, k_cache, kbar, attention_bias, positions, ci, selects):
        """One token a row on a layer under the block selection: ``(the
        bias [B, H, 1, S] of the dense read, masked to the chosen blocks'
        slots; kbar with the kernel this token completes)``. The token at
        position ``t`` completes kernel ``(t - kernel + 1) / stride`` where
        that is a whole number: the mean of the cache's last ``kernel`` keys."""
        cfg = self.config
        B, S = k_cache.shape[:2]
        H, KV = cfg.num_heads, cfg.kv_heads
        kernel, stride, block = cfg.sparse_kernel, cfg.sparse_stride, cfg.sparse_block
        t = positions[:, 0]
        with jax.named_scope("trlx/block_select"):
            last = jax.lax.dynamic_slice_in_dim(k_cache, jnp.maximum(ci - kernel + 1, 0), kernel, axis=1)
            last = lane_unpack(last, k_cache.shape[-1] // kbar.shape[-1])  # (the cache's rows may hold heads side by side)
            mean = (jnp.sum(last.astype(jnp.float32), axis=1) / kernel).astype(kbar.dtype)  # [B, KV, D]
            completes = (t >= kernel - 1) & ((t - kernel + 1) % stride == 0)
            at = jnp.where(completes, (t - kernel + 1) // stride, kbar.shape[2])  # past the end: dropped
            kbar = kbar.at[jnp.arange(B), :, at].set(mean, mode="drop", unique_indices=True)
        if not selects:
            return attention_bias, kbar
        n_blocks = -(-S // block)
        chosen = select_blocks(q, kbar, t[:, None], n_blocks, cfg)[:, :, 0]  # [B, KV, NB]
        with jax.named_scope("trlx/block_select"):
            # a slot's block, from its position in its row, as a 0/1 matrix [B, NB, S]: the chosen blocks
            # reach their slots by one small product (a gather of 131,072 single elements took 1.6 ms a
            # layer a step on a v5e, a quarter of the step: PERF.md section 6, PR 49)
            at_position = jnp.arange(S)[None, :] - (ci - t)[:, None]
            of_block = (at_position[:, None, :] // block == jnp.arange(n_blocks)[None, :, None]).astype(jnp.bfloat16)
            kept = jnp.einsum("bkn,bns->bks", chosen.astype(jnp.bfloat16), of_block, preferred_element_type=jnp.float32) > 0.5
            bias = attention_bias + jnp.where(jnp.repeat(kept, H // KV, axis=1), 0.0, -1e9)[:, :, None, :]
        return bias, kbar


class _Projection(nn.Module):
    """The parameters of a bias-free projection, handed back as arrays where
    a ``Dense`` (or ``LoRADense``, if ``name`` is a LoRA target) would apply
    them: the same leaves under the same names and initialisers (``kernel``,
    and ``lora_a`` / ``lora_b`` beside it), for a projection that is used in
    two forms or inside a loop over row pieces. ``project`` applies them."""

    config: TransformerConfig
    shape: Tuple[int, int]
    axes: Tuple[str, ...]
    std: float = 0.02

    @nn.compact
    def __call__(self) -> Dict[str, jax.Array]:
        cfg = self.config
        init = param_with_axes(nn.initializers.normal(self.std), self.axes)
        p = {"kernel": self.param("kernel", init, self.shape, cfg.param_dtype)}
        if cfg.lora_r and self.name in cfg.lora_targets:
            p["lora_a"] = self.param("lora_a", nn.initializers.he_uniform(), (self.shape[0], cfg.lora_r), cfg.param_dtype)
            p["lora_b"] = self.param("lora_b", nn.initializers.zeros, (cfg.lora_r, self.shape[1]), cfg.param_dtype)
        return {k: v.astype(cfg.dtype) for k, v in p.items()}


def project(p: Dict[str, jax.Array], x: jax.Array, cfg: TransformerConfig) -> jax.Array:
    """``x W``, plus ``(alpha / r) x A B`` where ``p`` carries an adapter
    (``LoRADense``'s arithmetic)."""
    y = x @ p["kernel"]
    if "lora_a" in p:
        y = y + (x @ p["lora_a"]) @ p["lora_b"] * (cfg.lora_alpha / cfg.lora_r)
    return y


# An expanded pass builds q and k of heads x 192 (256 lanes on a TPU) and v
# and o of heads x 128 a token, and the flash kernel's lane-padded logsumexp:
# 260 KB a token at 128 heads, 10.6 GB for the 40,960 tokens of a 64-row
# scoring forward at width 640 (compiled for a described v5e, PR 40: 15.0 GiB
# of temporaries beside 8.0 GiB of arguments). Rows do not interact, so past
# LATENT_MAX_TOKENS the expanded form runs equal pieces of whole rows, of at
# most that many tokens, one after another (``latent_row_pieces``). A train
# step's minibatch (8 x 640) and the sampler's prefill (64 x 128) are under it
# and run whole. A constant with its arithmetic, not a setting.
LATENT_MAX_TOKENS = 8192


def latent_row_pieces(rows: int, width: int, most: int = LATENT_MAX_TOKENS) -> int:
    """How many equal pieces of whole rows an expanded latent-attention pass
    (a KDA layer's, under its own ``most``) runs in: 1 up to ``most`` tokens,
    else the fewest that divide ``rows`` into pieces of at most that many (one
    row a piece at worst)."""
    if rows * width <= most:
        return 1
    fewest = -(-rows * width // most)
    return next((n for n in range(fewest, rows) if rows % n == 0), rows)


# A pass under a learned selection (``index_topk`` below the row's length)
# builds the selection, and where no flash kernel runs (``attention_impl:
# xla``, the CPU) attends under it too, in blocks of SPARSE_Q_BLOCK queries
# against the keys up to the end of their group of SPARSE_KEY_GROUP: float32
# index scores (and, on the einsum path, attention scores) of one block at a
# time. Groups keep the causal half out of the products: at 8192 tokens 62.5%
# of the square is computed where 50% is causal. On the chip the attention
# itself goes through the flash kernels with the selection as one more mask a
# tile (``ops/flash_attention.py``, ``selection=``): XLA's softmax over a
# block's ``f32[64, 128, 8192]`` scores took 23.6 ms where the products took
# 1.5 (PERF.md section 6, PR 42). Constants with their arithmetic, not
# settings.
SPARSE_Q_BLOCK = 128
SPARSE_KEY_GROUP = 2048


def _query_blocks(T: int, fn: Callable[[Any, int, int], jax.Array]) -> List[jax.Array]:
    """``fn(start, n_queries, n_keys)`` for every block of queries of a row of
    ``T`` tokens, in order: queries ``[start, start + n_queries)`` against keys
    ``[0, n_keys)``, ``n_keys`` the end of the block's group. Each result is
    ``[b, n_queries, ...]``; a group's equal blocks run under one ``lax.map``
    (``start`` is then traced) and come back joined along axis 1."""
    outs = []
    for g0 in range(0, T, SPARSE_KEY_GROUP):
        g1 = min(g0 + SPARSE_KEY_GROUP, T)
        tq = min(SPARSE_Q_BLOCK, g1 - g0)
        n = (g1 - g0) // tq
        if n == 1:
            outs.append(fn(g0, tq, g1))
        else:
            y = jax.lax.map(lambda i: fn(g0 + i * tq, tq, g1), jnp.arange(n))  # [n, b, tq, ...]
            outs.append(jnp.moveaxis(y, 0, 1).reshape(y.shape[1], n * tq, *y.shape[3:]))
        if (g1 - g0) % tq:
            outs.append(fn(g0 + n * tq, (g1 - g0) % tq, g1))
    return outs


def selected_frac(width: int, topk: int) -> float:
    """Pairs (query, key) a learned selection of ``topk`` keeps of a row's
    causal pairs at ``width`` tokens, no padding: query ``t`` keeps ``min(t +
    1, topk)``. Host arithmetic for ``learn/attn_selected_frac``."""
    kept = min(width, topk)
    return (kept * (kept + 1) // 2 + (width - kept) * topk) / max(width * (width + 1) // 2, 1)


def sparse_gather_rows(cfg: "TransformerConfig", slots: int) -> int:
    """Rows of the cache a single-token step gathers for one row of the batch
    on a cache of ``slots`` slots, summed over the layers: ``index_topk`` on
    every layer that attends under the selection (``LatentAttention``: each
    chosen slot is one row of the ``latent`` leaf, fetched once), 0 where the
    selection does not bind. Host arithmetic for ``rollout/sparse_gather_rows``."""
    if not cfg.index_topk or slots <= cfg.index_topk:
        return 0
    return cfg.index_topk * sum(layout.indexer is not None for layout in cfg.layer_layouts)


def largest_k(x: jax.Array, k: int) -> jax.Array:
    """Boolean mask of the ``k`` largest entries along the last axis of a
    float32 ``x`` (every entry where there are fewer than ``k``); of entries
    equal to the ``k``-th value the first by position count, as
    ``jax.lax.top_k`` orders them. Exact, without a sort: float32 maps onto
    uint32 in order, and 32 rounds of counting fix the ``k``-th largest key a
    bit at a time."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    key = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    count = functools.partial(jnp.sum, axis=-1, dtype=jnp.int32)

    def fix_bit(i, found):
        trial = found | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        return jnp.where(count(key >= trial[..., None]) >= k, trial, found)

    kth = jax.lax.fori_loop(0, 32, fix_bit, jnp.zeros(x.shape[:-1], jnp.uint32))[..., None]
    above, at = key > kth, key == kth
    room = k - count(above)[..., None]
    # the cumulative count is needed only where a tie straddles the k-th place
    return above | jax.lax.cond(
        jnp.any(count(at)[..., None] > room),
        lambda: at & (jnp.cumsum(at, axis=-1, dtype=jnp.int32) <= room),
        lambda: at,
    )


def index_scores(q_i: jax.Array, k_i: jax.Array, w: jax.Array) -> jax.Array:
    """``I[.., t, s] = sum_j w[.., t, j] relu(q_i[.., t, j] . k_i[.., s])`` in
    float32: ``q_i [b, T, HI, DI]`` against the ONE key a slot ``k_i [b, S,
    DI]``, weighted by ``w [b, T, HI]``."""
    with jax.named_scope("trlx/attn_index_scores"):
        dots = jnp.einsum("bthd,bsd->bths", q_i, k_i, preferred_element_type=jnp.float32)
        return jnp.sum(jax.nn.relu(dots) * w.astype(jnp.float32)[..., None], axis=2)


def select_keys(q_i, k_i, w, visible, topk: int) -> jax.Array:
    """The selection of a whole row ``[b, T, T]`` (bool): for each query the
    ``topk`` visible keys of the largest index score, every visible key where
    there are fewer. ``visible(start, n_queries, n_keys)`` is the block's
    causal and padding mask."""
    T = q_i.shape[1]

    def block(start, tq, tk):
        rows = lambda a: jax.lax.dynamic_slice_in_dim(a, start, tq, axis=1)
        scores = index_scores(rows(q_i), k_i[:, :tk], rows(w))
        with jax.named_scope("trlx/attn_index_select"):
            seen = visible(start, tq, tk)
            chosen = largest_k(jnp.where(seen, scores, -jnp.inf), topk) & seen
            return jnp.pad(chosen, ((0, 0), (0, 0), (0, T - tk)))

    return jnp.concatenate(_query_blocks(T, block), axis=1)


def selected_attention(q, k, v, visible, selection, dtype, selection_block: int = 1) -> jax.Array:
    """``grouped_einsum_attention``'s function of MHA ``q``, ``k [b, T, H,
    D]`` and ``v [b, T, H, Dv]`` with each query's softmax over its selected
    visible keys only (``selection [b, T, T]``), a block of queries at a time;
    a block's scores are recomputed in the backward pass, never kept. A
    selection by blocks of keys, ``[b, KV, T, T / selection_block]``, holds one
    set for the query heads of each of ``k``'s and ``v``'s ``KV`` heads (GQA)."""
    b, T, H, D = q.shape
    if selection.ndim == 4:
        KV = k.shape[2]

        @functools.partial(jax.checkpoint, static_argnums=(1, 2))
        def grouped(start, tq, tk):
            sel = jax.lax.dynamic_slice_in_dim(selection, start, tq, axis=2)[..., : -(-tk // selection_block)]
            keep = visible(start, tq, tk)[:, None] & jnp.repeat(sel, selection_block, axis=-1)[..., :tk]
            rows = jax.lax.dynamic_slice_in_dim(q, start, tq, axis=1).reshape(b, tq, KV, H // KV, D)
            scores = jnp.einsum("bqkgd,bskd->bkgqs", rows, k[:, :tk], preferred_element_type=jnp.float32)
            scores = jnp.where(keep[:, :, None], scores / np.sqrt(D), -1e9)
            probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
            return jnp.einsum("bkgqs,bskd->bqkgd", probs, v[:, :tk]).reshape(b, tq, H, v.shape[-1])

        return jnp.concatenate(_query_blocks(T, grouped), axis=1)

    @functools.partial(jax.checkpoint, static_argnums=(1, 2))
    def block(start, tq, tk):
        with jax.named_scope("trlx/attn_sparse"):
            keep = visible(start, tq, tk) & jax.lax.dynamic_slice(selection, (0, start, 0), (b, tq, tk))
            rows = jax.lax.dynamic_slice_in_dim(q, start, tq, axis=1)
            scores = jnp.einsum("bqhd,bkhd->bhqk", rows, k[:, :tk], preferred_element_type=jnp.float32)
            scores = jnp.where(keep[:, None], scores / np.sqrt(D), -1e9)
            probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
            return jnp.einsum("bhqk,bkhd->bqhd", probs, v[:, :tk])

    return jnp.concatenate(_query_blocks(T, block), axis=1)


def select_slots(q_i, k_i, w, attention_bias, cache_index, kv_extents, topk: int) -> jax.Array:
    """One query token's selection ``[B, topk]`` (slot numbers) over the index
    keys of the cache ``k_i [B, S, DI]``, ``S > topk``: the ``topk`` visible
    slots of the largest index score. Where fewer are visible the rest are
    ``-1``: ``top_k`` hands back the values beside the places, and a pick is
    a masked slot exactly when its value is the ``-inf`` the mask put there,
    so whoever attends over the selection (this layer and those that borrow
    it) needs no second look at the bias.
    Over the shortest of ``kv_extents`` (never under ``topk`` slots) that
    holds the slot just written, as ``extent_attention``."""

    def over(extent):
        def pick(q_i, k_i, w, bias):
            n = k_i.shape[1] if extent is None else max(extent, topk)
            scores = index_scores(q_i[:, None], k_i[:, :n], w[:, None])[:, 0]
            with jax.named_scope("trlx/attn_index_select"):
                values, slots = jax.lax.top_k(jnp.where(bias[:, 0, 0, :n] > -1.0, scores, -jnp.inf), topk)
                return jnp.where(values > -jnp.inf, slots, -1)

        return pick

    if kv_extents is None or len(kv_extents) < 2:
        return over(None)(q_i, k_i, w, attention_bias)
    return _switch_on_extent(cache_index, kv_extents, over, q_i, k_i, w, attention_bias)


# ---------------------------------------------------------------------------
# a selection by BLOCKS of keys over a plain K/V cache (`sparse_topk`)
# ---------------------------------------------------------------------------


def roll_rows(a: jax.Array, shift: jax.Array) -> jax.Array:
    """``out[b, i] = a[b, (i + shift[b]) mod T]`` along axis 1: each row of a
    left-padded batch moved to its own first real token (``shift`` the pads in
    front of it) and, by ``-shift``, back."""
    T = a.shape[1]
    at = (jnp.arange(T)[None, :] + shift[:, None]) % T
    return jnp.take_along_axis(a, at.reshape(at.shape + (1,) * (a.ndim - 2)), axis=1, mode="promise_in_bounds")


def pooled_keys(k: jax.Array, cfg: "TransformerConfig") -> jax.Array:
    """The compressed keys ``[B, KV, NK, D]`` of ``k [B, T, KV, D]``, a row's
    keys from its first real token on with zeros behind its last: ``kbar_j``
    the mean of keys ``[stride j, stride j + kernel)``, float32 sums, in
    ``k``'s dtype as the cache holds them. ``NK = T // stride``: the last
    ``kernel / stride - 1`` of them reach past the row and are never valid."""
    B, T, KV, D = k.shape
    stride, per = cfg.sparse_stride, cfg.sparse_kernel // cfg.sparse_stride
    n = T // stride
    strides = k[:, : n * stride].astype(jnp.float32).reshape(B, n, stride, KV, D).sum(axis=2)
    strides = jnp.pad(strides, ((0, 0), (0, per - 1), (0, 0), (0, 0)))
    kbar = sum(strides[:, i : i + n] for i in range(per)) / cfg.sparse_kernel
    return kbar.transpose(0, 2, 1, 3).astype(k.dtype)


def select_blocks(q: jax.Array, kbar: jax.Array, t: jax.Array, n_blocks: int, cfg: "TransformerConfig") -> jax.Array:
    """The blocks ``[B, KV, tq, n_blocks]`` (bool) the queries ``q [B, tq, H,
    D]`` at positions ``t [B | 1, tq]`` attend over, one set for the ``H /
    KV`` query heads of a KV group: each head's softmax over the compressed
    keys ``kbar [B, KV, NK, D]`` that are complete at ``t`` (``stride j +
    kernel - 1 <= t``), summed over the group; a block scores the largest of
    the kernels that overlap it; the first ``SPARSE_INIT_BLOCKS`` blocks and
    every block with a key in ``(t - sparse_window, t]`` are chosen whatever
    they score, the best others until ``sparse_topk`` are (of equal scores the
    lower block, as ``largest_k``); never a block past the query's own.
    Parameter-free, float32, and without a gradient."""
    B, tq, H, D = q.shape
    KV, NK = kbar.shape[1], kbar.shape[2]
    block, stride, reach = cfg.sparse_block, cfg.sparse_stride, cfg.sparse_kernel // cfg.sparse_stride - 1
    per = block // stride
    with jax.named_scope("trlx/block_select"):
        q, kbar = jax.lax.stop_gradient(q), jax.lax.stop_gradient(kbar)
        s = jnp.einsum("bqkgd,bkjd->bkgqj", q.reshape(B, tq, KV, H // KV, D), kbar, preferred_element_type=jnp.float32)
        done = (stride * jnp.arange(NK) + cfg.sparse_kernel - 1 <= t[..., None])[:, None, None]  # [B | 1, 1, 1, tq, NK]
        s = jnp.where(done, s / np.sqrt(D), -jnp.inf)
        e = jnp.where(done, jnp.exp(s - jnp.maximum(jnp.max(s, axis=-1, keepdims=True), -1e30)), 0.0)
        r = jnp.sum(e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30), axis=2)  # [B, KV, tq, NK]
        # block b overlaps kernels per * b - reach .. per * b + per - 1
        r = jnp.pad(jnp.where(done[:, :, 0], r, -jnp.inf), ((0, 0), (0, 0), (0, 0), (reach, max(per * n_blocks - NK, 0))),
                    constant_values=-jnp.inf)
        score = functools.reduce(jnp.maximum, [r[..., i : i + per * n_blocks : per] for i in range(per + reach)])
        blocks = jnp.arange(n_blocks)
        own = (t // block)[..., None]  # the query's own block
        causal = (blocks <= own)[:, None]
        forced = ((blocks < SPARSE_INIT_BLOCKS) | (blocks >= (jnp.maximum(t - cfg.sparse_window + 1, 0) // block)[..., None]))[:, None]
        score = jnp.where(causal, jnp.where(forced, jnp.inf, score), -jnp.inf)
        return largest_k(score, cfg.sparse_topk) & causal


def block_selected_pairs(width: int, cfg: "TransformerConfig") -> Tuple[float, float]:
    """``(chosen, causal)`` (query, key) pairs of a row of ``width`` tokens, no
    padding, on a layer under the block selection: a query at position ``t``
    keeps every key while its causal blocks number ``sparse_topk`` or fewer,
    else ``sparse_topk - 1`` whole blocks and its own up to itself; all of
    them on a row under ``sparse_dense_len``. Host arithmetic for
    ``learn/attn_block_selected_frac``."""
    t = np.arange(width, dtype=np.int64)
    kept = t + 1
    if cfg.sparse_topk and width >= cfg.sparse_dense_len:
        bound = (cfg.sparse_topk - 1) * cfg.sparse_block + t % cfg.sparse_block + 1
        kept = np.where(t // cfg.sparse_block + 1 > cfg.sparse_topk, bound, kept)
    return float(kept.sum()), float(width * (width + 1) // 2)


def block_selected_steps(prompt: int, new: int, cfg: "TransformerConfig") -> float:
    """Chosen blocks over causal blocks of the decode steps that write slots
    ``[prompt, prompt + new)`` of an unpadded row, mean over the steps. Host
    arithmetic for ``rollout/attn_block_selected_frac``."""
    causal = np.arange(prompt, prompt + new, dtype=np.int64) // cfg.sparse_block + 1
    if not cfg.sparse_topk or prompt + new < cfg.sparse_dense_len or not new:
        return 1.0
    return float(np.mean(np.minimum(causal, cfg.sparse_topk) / causal))


def block_sparse_attention(cfg, q, k, v, visible, chosen_of, dtype, flash_args=None) -> jax.Array:
    """``[B, T, H, D]``: causal GQA attention of ``q [B, T, H, D]`` over the
    row's own ``k``, ``v [B, T, KV, D]``, rows in position space (``roll_rows``),
    each query's softmax over the keys of its chosen blocks: ``chosen_of(start,
    n_queries, n_keys)`` gives a block of queries' sets ``[B, KV, n_queries,
    NB]``, ``visible(start, n_queries, n_keys)`` their causal and padding mask.
    Through the flash kernels under ``selection=`` where ``flash_args`` (their
    key mask) is given, else through ``selected_attention``'s masked einsums."""
    T = q.shape[1]
    n_blocks = -(-T // cfg.sparse_block)

    def padded(start, tq, tk):  # queries on axis 1, as `_query_blocks` joins them
        sel = chosen_of(start, tq, tk)
        return jnp.pad(sel, ((0, 0), (0, 0), (0, 0), (0, n_blocks - sel.shape[-1]))).transpose(0, 2, 1, 3)

    selection = jnp.concatenate(_query_blocks(T, padded), axis=1).transpose(0, 2, 1, 3)  # [B, KV, T, NB]
    with jax.named_scope("trlx/block_attn"):
        if flash_args is not None:
            return _flash_attention(q, k, v, {**flash_args, "selection": selection, "selection_block": cfg.sparse_block})
        return selected_attention(q, k, v, visible, selection, dtype, cfg.sparse_block)


class Indexer(nn.Module):
    """The projections of a ``full`` layer's indexer: ``index_heads`` queries
    of ``index_head_dim`` from the layer's normed query latent, ONE key a
    token from the layer's input through a LayerNorm, rotary embedding on the
    first ``qk_rope_head_dim`` dims of both, and the heads' weights ``x WIw /
    sqrt(index_heads * index_head_dim)`` (float32). No bias, no adapter, and
    no gradient: ``top_k`` has none, and the loss the publication trains these
    on is not PPO's."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, cq, x, sin, cos):
        cfg = self.config
        HI, DI, dr = cfg.index_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
        taken = {"wq_b", "wk", "weights_proj"} & set(cfg.lora_targets)
        if cfg.lora_r and taken:
            raise ValueError(
                f"the indexer takes no LoRA adapter ({sorted(taken)}): its selection has no gradient "
                "(LatentAttention); adapt q_a_proj, q_b_proj, kv_a_proj, o_proj"
            )
        cq, x = jax.lax.stop_gradient(cq), jax.lax.stop_gradient(x)
        q_i = _dense(cfg, HI * DI, False, ("latent", "joined_kv"), "wq_b")(cq).reshape(*cq.shape[:2], HI, DI)
        k_i = _dense(cfg, DI, False, ("embed", "latent"), "wk")(x)
        k_i = nn.LayerNorm(epsilon=1e-6, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="k_norm")(k_i)
        w = _dense(cfg, HI, False, ("embed", "latent"), "weights_proj")(x).astype(jnp.float32) / np.sqrt(HI * DI)
        q_i = apply_rotary(q_i, sin, cos, dr, True)
        k_i = apply_rotary(k_i[:, :, None, :], sin, cos, dr, True)[:, :, 0]
        return q_i, k_i, w


class LatentAttention(nn.Module):
    """Multi-head latent attention with an explicit latent cache.

    ``cq = RMS(x Wqa)``; ``q = cq Wqb`` gives each head ``[q_n | q_r]``
    (under ``q_lora_rank`` 0 there is no query latent: ``q = x Wq``, ONE
    ``q_proj``, no ``q_a_proj`` and no ``q_a_norm``, and no learned selection);
    ``[ckv | k_r] = x Wkva``, ``c = RMS(ckv)``, ONE ``k_r`` for all heads;
    rotary embedding on ``q_r`` and ``k_r`` only (split-half pairs; none where
    the layer's ``rotary`` is False, NoPE: both stay as projected, in the
    expanded form, the absorbed form and the cache alike);
    ``[k_n | v]`` a head ``= c Wkvb``; scores ``(q_n . k_n + q_r . k_r) /
    sqrt(dn + dr)``, causal softmax in float32, output ``concat(sum p v) Wo``.

    Two forms of that one function. **Expanded** (a pass without a cache:
    scoring, hydra branch, train step; and the sampler's prefill): per-head
    K ``[B, T, H, dn + dr]`` and V ``[B, T, H, dv]`` are built from ``c`` and
    go through the flash kernel or the einsum path like any other head,
    with unlike q/k and v sizes, in pieces of whole rows where the pass is
    long (``latent_row_pieces``). **Absorbed** (a single-token step on a
    cache): the key half of ``Wkvb`` is folded into the query (``q_c = q_n
    Wkvb_k^T``, ``[B, H, r]``) and its value half into the output (``o =
    (sum p c) Wkvb_v``), so the step attends over the latent itself
    (``absorbed_latent_attention``) and never builds K or V of the row.

    **Under a learned selection** (``indexer`` ``full`` or ``shared``:
    ``cfg.index_topk`` > 0) a query's softmax runs over the ``index_topk``
    visible keys of the largest index score (``index_scores``) only. A
    ``full`` layer makes the selection from its own ``Indexer``; a ``shared``
    layer is handed the one in force (``selection``) and holds no indexer.
    Both forms honour it and hand it on: an expanded pass of more than
    ``index_topk`` tokens as a mask ``[B, T, T]`` built over blocks of queries
    (``select_keys``) and applied by the flash kernels as one more mask a tile
    (``flash_attention(..., selection=)``; by ``selected_attention``'s masked
    einsums where no kernel runs), a single-token step on more than
    ``index_topk`` slots as slot numbers ``[B, index_topk]``
    (``select_slots``; ``-1`` where fewer slots are visible), the chosen
    slots' rows of the cache gathered once and attended over in absorbed
    form. A row no longer than
    ``index_topk`` selects every causal key and runs as without an indexer
    (``selection`` None). The selection has no gradient.

    The cache is ``{"ckv": [B, S, r], "k_rope": [B, S, dr]}``; on a layer
    under a selection it is ONE leaf ``{"latent": [B, S, r + dr]}``, a slot's
    normed latent ``c`` in columns ``[0, r)`` beside its roped key ``k_r`` in
    ``[r, r + dr)`` (a step that gathers chosen slots fetches one row a slot:
    XLA's gather costs by the row, not the byte), with ``"k_index": [B, S,
    DI]`` on a ``full`` layer. All written at ``cache_index``
    (one scalar for all rows: the plain sampler). A span (prefill) must
    start at slot 0: it attends over its own keys, expanded, and leaves its
    latents in the cache. ``kv_b_proj`` takes no LoRA adapter: the absorbed
    form folds its matrix, not its output."""

    config: TransformerConfig
    layer: int = 0  # which layer's kind (`layer_layout`: indexer, rotary) and sizes (`attention_sizes`) this is
    lends: bool = False  # the next layer borrows the selection in force here

    @nn.compact
    def __call__(self, x, attention_bias, positions, cache=None, cache_index=None, flash_args=None, kv_extents=None, selection=None,
                 token_mask=None):
        """``(output, new cache, selection in force for a borrowing layer,
        gate statistics)``: the last is ``[sum over real tokens of a token's
        mean gate, real tokens]`` of a pass under a headwise gate, else None."""
        cfg = self.config
        B, T, _ = x.shape
        layout = cfg.layer_layout(self.layer)
        indexer, rotary = layout.indexer, layout.rotary
        H, rq, r, dn, dr, dv, theta, window = cfg.attention_sizes(self.layer)
        topk = cfg.index_topk if indexer else 0
        if cfg.lora_r and "head_gate" in cfg.lora_targets:
            raise ValueError(
                "head_gate takes no LoRA adapter: the headwise gate's projection is one number a head "
                "(LatentAttention); adapt q_a_proj, q_b_proj, kv_a_proj, o_proj"
            )
        if "kv_b_proj" in cfg.lora_targets and cfg.lora_r:
            raise ValueError(
                "kv_b_proj takes no LoRA adapter: a decode step folds its matrix into the "
                "query and the output (LatentAttention, absorbed form); adapt q_a_proj, "
                "q_b_proj, kv_a_proj, o_proj"
            )
        if cache is not None and "block_table" in cache:
            raise NotImplementedError("the paged Engine holds K and V blocks; a latent layer has none (ops/cache_layout.py::refuse)")

        def latent_norm(name):
            return nn.RMSNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                              scale_init=param_with_axes(nn.initializers.ones, ("latent",)), name=name)

        if rq:
            cq = latent_norm("q_a_norm")(_dense(cfg, rq, False, ("embed", "latent"), "q_a_proj")(x))
        else:  # no query latent: ONE projection `q_proj` from the layer's input, which stands where `cq` does below
            cq = x
        kv_a = _dense(cfg, r + dr, False, ("embed", "latent"), "kv_a_proj")(x)
        c = latent_norm("kv_a_norm")(kv_a[..., :r])
        if cfg.mla_lora_rescale:
            # the normed latents at the residual stream's size, sqrt(hidden / rank) each: what both
            # forms read and what the cache holds; `k_r` is neither normed nor scaled
            c = c * np.sqrt(cfg.hidden_size / r).astype(c.dtype)
            if rq:
                cq = cq * np.sqrt(cfg.hidden_size / rq).astype(cq.dtype)
        if rq:
            q_b = _Projection(cfg, (rq, H * (dn + dr)), ("latent", "joined_kv"), cfg.qk_init_std, name="q_b_proj")()
        else:
            q_b = _Projection(cfg, (cfg.hidden_size, H * (dn + dr)), ("embed", "joined_kv"), cfg.qk_init_std, name="q_proj")()
        w_kvb = _Projection(cfg, (r, H * (dn + dv)), ("latent", "joined_kv"), name="kv_b_proj")()["kernel"].reshape(r, H, dn + dv)
        o = _Projection(cfg, (H * dv, cfg.hidden_size), ("joined_kv", "embed"), name="o_proj")()
        gate = None
        if cfg.attention_gate_type == "headwise":
            with jax.named_scope("trlx/attn_head_gate"):
                gate = jax.nn.sigmoid(_dense(cfg, H, False, ("embed", "heads"), "head_gate")(x))  # [B, T, H]

        sin, cos = rotary_sin_cos(positions, dr, theta)
        roped = (lambda a, sin, cos: apply_rotary(a, sin, cos, dr, True)) if rotary else (lambda a, sin, cos: a)
        k_r = roped(kv_a[..., None, r:], sin, cos)[:, :, 0]  # [B, T, dr]: one head
        index = Indexer(cfg, name="indexer")(cq, x, sin, cos) if indexer == "full" else None

        def queries(cq, sin, cos):
            q = project(q_b, cq, cfg).reshape(*cq.shape[:2], H, dn + dr)
            return q[..., :dn], roped(q[..., dn:], sin, cos)

        new_cache = None
        ring = kv_extents is not None and kv_extents.ring
        if cache is not None:
            ci = jnp.asarray(cache_index)
            if ci.ndim:
                raise NotImplementedError("a latent cache is written at one scalar cache_index for all rows (the plain sampler)")
            if ring:
                # a window layer's ring of C latents, slot t at t mod C (CausalTransformer._ring_plan built
                # the bias / flash_args to match), written as `Attention`'s ring of K and V is
                C = cache["ckv"].shape[1]
                if T == 1:
                    write = lambda leaf, a: jax.lax.dynamic_update_slice(leaf, a.astype(leaf.dtype), (0, ci % C, 0))
                elif T <= C:  # a prefill from slot 0 that does not wrap
                    write = lambda leaf, a: jax.lax.dynamic_update_slice(leaf, a.astype(leaf.dtype), (0, 0, 0))
                else:  # a prefill from slot 0: its last C positions stay
                    write = lambda leaf, a: jnp.roll(a[:, T - C :].astype(leaf.dtype), (T - C) % C, axis=1)
                new_cache = {"ckv": write(cache["ckv"], c), "k_rope": write(cache["k_rope"], k_r)}
            elif "latent" in cache:  # a layer under a selection: one row a slot, written as its two column ranges
                rows = jax.lax.dynamic_update_slice(cache["latent"], c.astype(cache["latent"].dtype), (0, ci, 0))
                new_cache = {"latent": jax.lax.dynamic_update_slice(rows, k_r.astype(rows.dtype), (0, ci, r))}
            else:
                new_cache = {
                    "ckv": jax.lax.dynamic_update_slice(cache["ckv"], c.astype(cache["ckv"].dtype), (0, ci, 0)),
                    "k_rope": jax.lax.dynamic_update_slice(cache["k_rope"], k_r.astype(cache["k_rope"].dtype), (0, ci, 0)),
                }
            if index is not None:
                new_cache["k_index"] = jax.lax.dynamic_update_slice(cache["k_index"], index[1].astype(cache["k_index"].dtype), (0, ci, 0))
        step = cache is not None and T == 1
        # the selection binds where the keys in reach outnumber it: a step's cache slots, a pass's tokens
        selects = bool(topk) and (cache_slots(cache) if step else T) > topk
        if selects and index is None and selection is None:
            raise ValueError("a layer whose indexer type is `shared` was handed no selection")
        if step:
            q_n, q_r = queries(cq, sin, cos)
            q_c = jnp.einsum("bhn,rhn->bhr", q_n[:, 0], w_kvb[..., :dn])
            bias = attention_bias
            extents = kv_extents.slots if kv_extents is not None else None
            if selects:
                if index is not None:
                    selection = select_slots(index[0][:, 0], new_cache["k_index"], index[2][:, 0], bias, ci, extents, topk)
                with jax.named_scope("trlx/attn_sparse"):
                    # each chosen slot's one row. A pick of -1 is a masked slot (select_slots):
                    # the bias over a step's slots is 0 or -1e9 and nothing else here
                    # (_attention_bias: rotary positions, no window under a selection), so over
                    # the chosen ones it is this, term for term, without a gather of its own.
                    # top_k's places clamped at 0 are slots of the cache: no fill pass over the rows
                    rows = jnp.take_along_axis(new_cache["latent"], jnp.maximum(selection, 0)[:, :, None], axis=1, mode="promise_in_bounds")
                    bias = jnp.where(selection >= 0, 0.0, -1e9)[:, None, None, :]
                extents = None
            else:  # every slot, as without an indexer
                selection, rows = None, new_cache.get("latent")
            ckv, k_rope = (rows[..., :r], rows[..., r:]) if rows is not None else (new_cache["ckv"], new_cache["k_rope"])
            with jax.named_scope("trlx/attn_latent_ring") if ring else contextlib.nullcontext():
                o_c = absorbed_latent_attention(q_c, q_r[:, 0], ckv, k_rope, bias, ci, extents, 1.0 / np.sqrt(dn + dr), cfg.dtype)
            out = jnp.einsum("bhr,rhv->bhv", o_c, w_kvb[..., dn:])
            if gate is not None:
                out = out * gate[:, 0, :, None]
            return project(o, out.reshape(B, 1, H * dv), cfg), new_cache, (selection if self.lends else None), None

        use_flash = flash_args is not None
        if use_flash and _maybe_ring_mesh(T) is not None:
            raise NotImplementedError(
                "ring attention over the mesh's `sequence` axis rotates per-head K and V chunks "
                "(parallel/ring_attention.py); latent attention is not built for it: use sequence=1"
            )

        def expanded(cq, c, k_r, sin, cos, visible, chosen, gate):
            """Whole rows ``[b, T, ...]``; ``visible`` is their key mask
            (flash) or their additive bias (einsum path); ``chosen`` what
            decides their selection: the indexer's ``(q_i, k_i, w)``, the
            selection handed in, or nothing; ``gate`` their headwise gate."""
            b = cq.shape[0]
            with jax.named_scope("trlx/attn_latent_expand"):
                q_n, q_r = queries(cq, sin, cos)
                kv = jnp.einsum("btr,rhd->bthd", c, w_kvb)
                k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r[:, :, None, :], (b, T, H, dr))], axis=-1)
                q, v = jnp.concatenate([q_n, q_r], axis=-1), kv[..., dn:]
            if selects:
                def seen(start, tq, tk):  # [b, tq, tk]: causal by slot, and no padded key
                    if not use_flash:
                        return jax.lax.dynamic_slice(visible[:, 0], (0, start, 0), (b, tq, tk)) > -1.0
                    causal = jnp.arange(tk)[None, :] <= start + jnp.arange(tq)[:, None]
                    return causal[None] & (visible[:, None, :tk] > 0)

                if index is not None:
                    chosen = select_keys(*chosen, seen, topk)
                if use_flash:  # the flash kernels under one more mask a tile
                    out = _flash_attention(q, k, v, {**flash_args, "key_mask": visible, "selection": chosen})
                else:
                    out = selected_attention(q, k, v, seen, chosen, cfg.dtype)
            elif use_flash:
                out = _flash_attention(q, k, v, {**flash_args, "key_mask": visible})
            else:
                out = grouped_einsum_attention(q, k, v, visible, cfg.dtype)
            if gate is not None:
                out = out * gate[..., None].astype(out.dtype)
            out = out.reshape(b, T, H * dv)
            if selects:
                # a group of rows at a time: on a whole [7168, H dv] piece the TPU compiler
                # (jax 0.9.0) runs out of scoped VMEM fusing o_proj's product with its adapter's
                groups = jnp.split(out, list(range(SPARSE_KEY_GROUP, T, SPARSE_KEY_GROUP)), axis=1)
                out = jnp.concatenate([project(o, rows, cfg) for rows in groups], axis=1)
            else:
                out = project(o, out, cfg)
            return out, (chosen if selects and index is not None and self.lends else None)

        chosen = (index if index is not None else selection) if selects else None
        operands = (cq, c, k_r, sin, cos, flash_args["key_mask"] if use_flash else attention_bias, chosen, gate)
        pieces = latent_row_pieces(B, T)
        with jax.named_scope("trlx/attn_latent_window") if window else contextlib.nullcontext():
            if pieces == 1:
                out, made = expanded(*operands)
            else:
                split = lambda a: a.reshape(pieces, B // pieces, *a.shape[1:])
                join = lambda a: a.reshape(B, *a.shape[2:])
                out, made = jax.tree_util.tree_map(join, jax.lax.map(lambda piece: expanded(*piece), jax.tree_util.tree_map(split, operands)))
        gate_stats = None
        if gate is not None:  # [a token's mean gate summed over the real tokens, real tokens]
            real = jnp.ones((B, T), jnp.float32) if token_mask is None else token_mask.astype(jnp.float32)
            gate_stats = jnp.stack([jnp.sum(jnp.mean(gate.astype(jnp.float32), axis=-1) * real), jnp.sum(real)])
        if not (selects and self.lends):
            return out, new_cache, None, gate_stats
        return out, new_cache, (made if index is not None else selection), gate_stats


# A gated MLP builds three [tokens, width] intermediates (gate, up, their
# product): 2.0 GB each for the 61,440 tokens of a 4-row prefill at 15360 under
# a width of 16384, which one v5e cannot hold beside 7 GB of weights. Tokens do
# not interact, so a forward whose intermediate would pass MLP_MAX_BYTES runs
# equal pieces of at most MLP_PIECE_BYTES one after another. The largest of
# the cells that came before (falcon-h1: 40,960 x 21,504 x 2 = 1.76 GB) is
# under the first number and keeps its program; a train step's one row of
# 16384 (0.54 GB) runs whole. Constants with their arithmetic, not settings.
MLP_MAX_BYTES = int(1.8 * 2**30)
MLP_PIECE_BYTES = 2**29


def mlp_token_pieces(tokens: int, token_bytes: int) -> int:
    """How many equal pieces a gated MLP cuts ``tokens`` into (``token_bytes``
    a token of one intermediate): 1 up to ``MLP_MAX_BYTES``, else the fewest
    that divide ``tokens`` into pieces of at most ``MLP_PIECE_BYTES``."""
    if tokens * token_bytes <= MLP_MAX_BYTES:
        return 1
    fewest = -(-tokens * token_bytes // MLP_PIECE_BYTES)
    return next((n for n in range(fewest, 64 * fewest) if tokens % n == 0), 1)


class MLP(nn.Module):
    config: TransformerConfig
    width: Optional[int] = None  # None = `intermediate_size`

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        width = self.width or cfg.intermediate_size
        act = get_activation(cfg.activation)
        pieces = mlp_token_pieces(int(np.prod(x.shape[:-1])), width * jnp.dtype(cfg.dtype).itemsize)
        if cfg.activation == "silu" and pieces > 1:
            if cfg.mlp_bias:
                raise NotImplementedError("a gated MLP in pieces (mlp_token_pieces) has no biases")
            gate_mult, down_mult = cfg.mlp_multipliers
            kernels = [_Projection(cfg, shape, axes, name=name)() for name, shape, axes in (
                ("gate_proj", (cfg.hidden_size, width), ("embed", "ffn")), ("up_proj", (cfg.hidden_size, width), ("embed", "ffn")),
                ("down_proj", (width, cfg.hidden_size), ("ffn", "embed")))]

            def piece(rows):
                gate, up = project(kernels[0], rows, cfg), project(kernels[1], rows, cfg)
                y = project(kernels[2], act(gate * gate_mult if gate_mult != 1.0 else gate) * up, cfg)
                return y * down_mult if down_mult != 1.0 else y

            return jax.lax.map(piece, x.reshape(pieces, -1, x.shape[-1])).reshape(x.shape)
        if cfg.activation == "silu":  # gated (llama-style) MLP
            gate = _dense(cfg, width, cfg.mlp_bias, ("embed", "ffn"), "gate_proj")(x)
            up = _dense(cfg, width, cfg.mlp_bias, ("embed", "ffn"), "up_proj")(x)
            gate_mult, down_mult = cfg.mlp_multipliers
            if gate_mult != 1.0:
                gate = gate * gate_mult
            h = act(gate) * up
            y = _dense(cfg, cfg.hidden_size, cfg.mlp_bias, ("ffn", "embed"), "down_proj")(h)
            return y * down_mult if down_mult != 1.0 else y
        h = act(_dense(cfg, width, cfg.mlp_bias, ("embed", "ffn"), "up_proj")(x))
        return _dense(cfg, cfg.hidden_size, cfg.mlp_bias, ("ffn", "embed"), "down_proj")(h)


def _conv_taps_init(key, shape, dtype):
    """torch's Conv1d default for a depthwise conv of width ``shape[0]``."""
    bound = 1.0 / np.sqrt(shape[0])
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound).astype(dtype)


# the Mamba-2 paper's initialisation: A uniform in [1, 16], dt log-uniform in
# [0.001, 0.1] through dt_bias (softplus's inverse)
def _a_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)


def _dt_bias_init(key, shape, dtype):
    dt0 = jnp.exp(jax.random.uniform(key, shape, jnp.float32, np.log(1e-3), np.log(1e-1)))
    return (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dtype)


class Mamba2Mixer(nn.Module):
    """Mamba-2 heads (``ops/ssd.py``): ``in_proj`` to ``z | x | B | C | dt``,
    a causal depthwise conv and SiLU over ``x, B, C``, the selective
    state-space recurrence a head, ``y * silu(z)`` under an RMSNorm over each
    group's channels, ``out_proj``.

    With a ``cache`` (a layer's dict: ``ssm [B, H, P, N]`` float32, ``conv
    [B, K-1, C]``) the recurrence starts from the stored state and the new
    state is returned: one token takes ``ssd_step``, a span (prefill) the
    chunked scan. Without one (scoring forward, hydra branch, train step) it
    starts from zero. ``token_mask [B, T]`` marks real tokens: a padded
    position contributes nothing, so a left-padded row reaches its first
    real token with a zero state and a zero conv window, and a row that has
    ended only decays its state."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, u, cache=None, token_mask=None):
        from trlx_tpu.ops.ssd import causal_conv, ssd_chunked, ssd_step

        cfg = self.config
        B, T, _ = u.shape
        H, P, G, N = cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_groups, cfg.mamba_state
        d_ssm, gn = cfg.mamba_d_ssm, cfg.mamba_groups * cfg.mamba_state
        keep = None if token_mask is None else token_mask.reshape(B, T, 1).astype(u.dtype)
        if keep is not None:
            u = u * keep
        if cfg.ssm_in_multiplier != 1.0:
            u = u * cfg.ssm_in_multiplier
        segments = (d_ssm, d_ssm, gn, gn, H)  # z, x, B, C, dt

        def in_proj_init(key, shape, dtype):
            # z, x, B, C columns at `mamba_in_proj_init_std` (Falcon-H1: 0.5): at
            # the 0.02 of every other matrix the family's multipliers leave B and
            # C so small that the state's part of a head's output is 1e-4 of the
            # skip D x, and a model run from random weights has a dead state. The
            # dt columns keep 0.02, so that the step sizes stay where dt_bias puts them
            std = jnp.full((shape[1],), cfg.mamba_in_proj_init_std).at[-H:].set(0.02)
            return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

        p = nn.Dense(
            sum(segments),
            use_bias=False,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=param_with_axes(in_proj_init, ("embed", "ssm")),
            name="in_proj",
        )(u)
        if any(m != 1.0 for m in cfg.ssm_multipliers):
            p = p * jnp.asarray(np.repeat(cfg.ssm_multipliers, segments), cfg.dtype)
        z, xbc, dt = jnp.split(p, [d_ssm, d_ssm + cfg.mamba_conv_channels], axis=-1)

        def vector(name, init, n):
            return self.param(name, param_with_axes(init, ("ssm",)), (n,), cfg.param_dtype)

        conv_w = self.param(
            "conv_weight", param_with_axes(_conv_taps_init, ("conv", "ssm")),
            (cfg.mamba_conv, cfg.mamba_conv_channels), cfg.param_dtype,
        )
        conv_b = vector("conv_bias", nn.initializers.zeros, cfg.mamba_conv_channels)
        xbc, conv_state = causal_conv(
            xbc, conv_w, conv_b, None if cache is None else cache["conv"]
        )
        xbc = nn.silu(xbc)
        if keep is not None:
            xbc = xbc * keep  # the conv's bias is not zero at a pad
        x, Bm, Cm = jnp.split(xbc, [d_ssm, d_ssm + gn], axis=-1)

        # (`_a_log_init`, `_dt_bias_init`: the paper's initialisation; D = 1)
        A = -jnp.exp(vector("A_log", _a_log_init, H).astype(jnp.float32))
        D = vector("D", nn.initializers.ones, H)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + vector("dt_bias", _dt_bias_init, H).astype(jnp.float32))

        x, Bm, Cm = x.reshape(B, T, H, P), Bm.reshape(B, T, G, N), Cm.reshape(B, T, G, N)
        state = None if cache is None else cache["ssm"]
        if cache is not None and T == 1:
            y, state = ssd_step(state, x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], D)
            y = y[:, None]
        else:
            scan = partial(ssd_chunked, chunk=cfg.mamba_chunk)
            if cache is None:
                # a backward pass runs the scan again rather than keep its
                # float32 decay matrices and chunk states: 166 KB a token a
                # block at the 34B widths, 45% of what the block saves,
                # against 0.6% of its FLOPs
                scan = jax.checkpoint(scan)
            y, state = scan(x, dt, A, Bm, Cm, D, token_mask, state)

        # gated norm, the gate first (mamba_norm_before_gate false): RMSNorm
        # over each group's channels, float32 statistics
        y = (y.reshape(B, T, d_ssm) * nn.silu(z)).astype(jnp.float32).reshape(B, T, G, d_ssm // G)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.layer_norm_epsilon)
        y = y.reshape(B, T, d_ssm).astype(cfg.dtype) * vector("norm_scale", nn.initializers.ones, d_ssm).astype(cfg.dtype)
        out = nn.Dense(
            cfg.hidden_size,
            use_bias=False,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=param_with_axes(nn.initializers.normal(0.02), ("ssm", "embed")),
            name="out_proj",
        )(y)
        new_cache = None if cache is None else {"ssm": state, "conv": conv_state.astype(cache["conv"].dtype)}
        return out, new_cache


class LightningMixer(nn.Module):
    """Lightning linear attention (Qin et al., arXiv:2401.04658) as
    ``minicpm_sala`` runs it: ``q, k, v`` of ``lightning_heads`` heads of
    ``lightning_head_dim``, an RMSNorm with a learned scale over each head's q
    and k, rotary embedding on all of a head's dims, then per head the linear
    recurrence ``S_t = lambda_h S_{t-1} + k_t v_t^T``, ``o_t = S_t^T q_t /
    sqrt(d)`` with the fixed decay ``lambda_h = exp(-2^(-8 h / H))`` (ALiBi's
    slopes) and no normaliser; ``o_proj(RMSNorm(concat_h o) * sigmoid(z_proj
    u))``. The recurrence is ``ops/ssd.py``'s, ``x = v``, ``B = k``, ``C = q /
    sqrt(d)``, ``A = log lambda``, every step 1, no groups and no skip.

    The layer's whole cache is ``{"state": [B, H, d, d]}`` float32 (``S``
    transposed: value channels by key channels), no K and no V: one token
    takes ``ssd_step``, a span (prefill) the chunked scan from the stored
    state, a pass without a cache the chunked scan from zero, run again in
    the backward pass rather than kept (``Mamba2Mixer``). ``token_mask``
    marks real tokens: a padded position feeds nothing into the state, so a
    left-padded row reaches its first real token with a zero state."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, u, positions, cache=None, token_mask=None):
        from trlx_tpu.ops.ssd import ssd_chunked, ssd_step

        cfg = self.config
        B, T, _ = u.shape
        H, D = cfg.lightning_heads, cfg.lightning_head_dim
        q = _dense(cfg, H * D, False, ("embed", "joined_kv"), "q_proj", cfg.qk_init_std)(u).reshape(B, T, H, D)
        k = _dense(cfg, H * D, False, ("embed", "joined_kv"), "k_proj", cfg.qk_init_std)(u).reshape(B, T, H, D)
        v = _dense(cfg, H * D, False, ("embed", "joined_kv"), "v_proj")(u).reshape(B, T, H, D)
        gate = jax.nn.sigmoid(_dense(cfg, H * D, False, ("embed", "joined_kv"), "z_proj")(u))
        if cfg.qk_norm:
            q, k = _qk_norm(cfg, "q_norm")(q), _qk_norm(cfg, "k_norm")(k)
        sin, cos = rotary_sin_cos(positions, D, cfg.rope_theta)
        q, k = apply_rotary(q, sin, cos, D, True), apply_rotary(k, sin, cos, D, True)
        q = q * jnp.asarray(1.0 / np.sqrt(D), q.dtype)
        if token_mask is not None:
            v = v * token_mask.reshape(B, T, 1, 1).astype(v.dtype)
        log_decay = -jnp.asarray(alibi_slopes(H), jnp.float32)

        if cache is not None and T == 1:
            with jax.named_scope("trlx/lightning_step"):
                y, state = ssd_step(cache["state"], v[:, 0], jnp.ones((1, H), jnp.float32), log_decay, k[:, 0], q[:, 0], None)
            y = y[:, None]
        else:
            scan = partial(ssd_chunked, chunk=cfg.lightning_chunk)
            if cache is None:
                scan = jax.checkpoint(scan)
            with jax.named_scope("trlx/lightning_scan"):
                y, state = scan(v, jnp.ones((1, T, H), jnp.float32), log_decay, k, q, None, None, None if cache is None else cache["state"])
        y = Norm(cfg, name="o_norm")(y.reshape(B, T, H * D)) * gate
        out = _dense(cfg, cfg.hidden_size, False, ("joined_kv", "embed"), "o_proj")(y)
        return out, (None if cache is None else {"state": state})


# A KDA layer's pass builds q, k, v, the log decays (float32) and the output of
# `kda_heads x kda_head_dim` a token: at 32 heads of 128 that is 16 KB a token
# an array, 2.1 GB an array for the 131,072 tokens of a 32-row scoring forward
# at width 4096. The forward of the chunked delta rule adds nothing to them at
# the published head size (a kernel that keeps a chunk on the chip,
# ops/delta_rule.py), but its backward pass, and the forward at any other head
# size, is plain jax.numpy and holds some fifteen float32 arrays of that size
# beside them. Rows do not interact, so past KDA_MAX_TOKENS the mixer runs
# equal pieces of whole rows, of at most that many tokens, one after another
# (`latent_row_pieces`'s arithmetic). A constant with its arithmetic, not a
# setting.
KDA_MAX_TOKENS = 8192


class KDAMixer(nn.Module):
    """Kimi Delta Attention (Kimi Linear report, arXiv:2510.26692) as
    ``kimi_linear`` runs it: ``q~, k~, v~`` of ``kda_heads`` heads of
    ``kda_head_dim`` each through its own causal depthwise conv of width
    ``kda_conv`` (no bias) and ``silu``; ``q = l2norm(q~) / sqrt(d)``, ``k =
    l2norm(k~)`` a head; a log decay a CHANNEL ``g = -exp(A_log_h)
    softplus(f_b(f_a u) + dt_bias)`` and a write strength a head ``beta =
    sigmoid(b u)``; per head the gated delta rule of ``ops/delta_rule.py``
    (float32); ``o_proj(concat_h(RMSNorm_d(o_h) * sigmoid(g_b(g_a u))_h))``, the
    norm over each head's own channels with one learned scale of ``d``.

    The layer's whole cache is ``{"state": [B, H, d, d]}`` float32 (key
    channels by value channels) and ``{"conv": [B, kda_conv - 1, 3 H d]}``,
    the last rows of ``[q~ | k~ | v~]`` before the convs; no K, no V: one
    token takes ``kda_step``, a span (prefill) the chunked form from the
    stored state, a pass without a cache the chunked form from zero, run
    again in the backward pass rather than kept (``LightningMixer``), in
    pieces of whole rows where the pass is long (``KDA_MAX_TOKENS``).
    ``token_mask`` marks real tokens: a padded position feeds nothing into
    the conv window or the state and does not decay it.

    Returns ``(out, new cache, statistics)``; the statistics of a pass
    without a cache are ``[sum of beta over real tokens (mean over heads),
    real tokens, the most negative log decay accumulated inside a chunk]``
    (``kda_summary``), else None."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, u, cache=None, token_mask=None):
        from trlx_tpu.ops.delta_rule import CHUNK, kda_chunked, kda_step
        from trlx_tpu.ops.ssd import causal_conv

        cfg = self.config
        B, T, E = u.shape
        H, D, K = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv
        W = H * D

        def proj(name, shape, axes):
            return _Projection(cfg, shape, axes, name=name)()

        def vector(name, init, n):
            return self.param(name, param_with_axes(init, ("ssm",)), (n,), cfg.param_dtype)

        qkv = [proj(name, (E, W), ("embed", "joined_kv")) for name in ("q_proj", "k_proj", "v_proj")]
        f_a, f_b = proj("f_a_proj", (E, D), ("embed", "latent")), proj("f_b_proj", (D, W), ("latent", "joined_kv"))
        g_a, g_b = proj("g_a_proj", (E, D), ("embed", "latent")), proj("g_b_proj", (D, W), ("latent", "joined_kv"))
        b_proj = proj("b_proj", (E, H), ("embed", "heads"))
        o_proj = proj("o_proj", (W, E), ("joined_kv", "embed"))

        # the Mamba-2 convention, as `Mamba2Mixer` draws its own: A a head, a step a channel through dt_bias
        conv_w = self.param("conv_weight", param_with_axes(_conv_taps_init, ("conv", "ssm")), (K, 3 * W), cfg.param_dtype)
        rate = jnp.exp(vector("A_log", _a_log_init, H).astype(jnp.float32))[:, None]  # [H, 1]
        dt_bias = vector("dt_bias", _dt_bias_init, W).astype(jnp.float32)
        o_scale = vector("o_norm_scale", nn.initializers.ones, D).astype(jnp.float32)
        step = cache is not None and T == 1

        def rows(u, keep, conv_state, state):
            """Whole rows ``u [b, T, E]`` with their mask ``keep [b, T]`` (or
            None) and, under a cache, their conv rows and state."""
            b = u.shape[0]
            real = None if keep is None else keep.reshape(b, T, 1).astype(u.dtype)
            if real is not None:
                u = u * real  # no bias anywhere below: a padded token's q~, k~, v~ are zero
            with jax.named_scope("trlx/kda_conv"):
                x = jnp.concatenate([project(p, u, cfg) for p in qkv], axis=-1)
                x, conv_state = causal_conv(x, conv_w, None, conv_state)
                q, k, v = jnp.split(nn.silu(x), 3, axis=-1)
                q, k = q.astype(jnp.float32).reshape(b, T, H, D), k.astype(jnp.float32).reshape(b, T, H, D)
                q = q * (jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) / np.sqrt(D))
                k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
                v = v.reshape(b, T, H, D)
            with jax.named_scope("trlx/kda_gate"):
                f = project(f_b, project(f_a, u, cfg), cfg).astype(jnp.float32) + dt_bias
                g = -rate * jax.nn.softplus(f).reshape(b, T, H, D)
                beta = jax.nn.sigmoid(project(b_proj, u, cfg).astype(jnp.float32))
                gate = jax.nn.sigmoid(project(g_b, project(g_a, u, cfg), cfg))
                if real is not None:  # ... and neither decays the state nor writes to it
                    g, beta = g * real[..., None].astype(jnp.float32), beta * real.astype(jnp.float32)
            stats = None
            if step:
                o, state = kda_step(state, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
                o = o[:, None]
            else:
                o, state = kda_chunked(q, k, v, g, beta, state)
                if cache is None:
                    in_chunk = jnp.pad(g, ((0, 0), (0, -T % CHUNK), (0, 0), (0, 0))).reshape(b, -1, CHUNK, H, D)
                    stats = jax.lax.stop_gradient(jnp.stack([
                        jnp.sum(jnp.mean(beta, axis=-1)), float(b * T) if keep is None else jnp.sum(keep.astype(jnp.float32)),
                        jnp.min(jnp.sum(in_chunk, axis=2)),
                    ]))
            # the norm over each head's own channels, float32 statistics, then the gate
            o = o.astype(jnp.float32)
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.layer_norm_epsilon) * o_scale
            out = project(o_proj, o.reshape(b, T, W).astype(cfg.dtype) * gate, cfg)
            return out, conv_state, state, stats

        conv_state, state = (None, None) if cache is None else (cache["conv"], cache["state"])
        pieces = 1 if step else latent_row_pieces(B, T, KDA_MAX_TOKENS)
        if cache is None:
            # a backward pass runs a piece again rather than keep its float32 gates, chunk matrices and states
            rows = jax.checkpoint(rows)
        if pieces == 1:
            out, conv_state, state, stats = rows(u, token_mask, conv_state, state)
        else:
            split = lambda a: a.reshape(pieces, B // pieces, *a.shape[1:])
            join = lambda a: a.reshape(B, *a.shape[2:])
            operands = jax.tree_util.tree_map(split, (u, token_mask, conv_state, state))
            out, conv_state, state, stats = jax.lax.map(lambda piece: rows(*piece), operands)
            out, conv_state, state = jax.tree_util.tree_map(join, (out, conv_state, state))
            if stats is not None:
                stats = jnp.stack([jnp.sum(stats[:, 0]), jnp.sum(stats[:, 1]), jnp.min(stats[:, 2])])
        new_cache = None if cache is None else {"state": state, "conv": conv_state.astype(cache["conv"].dtype)}
        return out, new_cache, stats


class ShortConvMixer(nn.Module):
    """LFM2's gated short convolution (``lfm2_moe``), a layer's whole
    sequence mixer: ``[B | C | z] = in_proj(u)`` (hidden to three times
    hidden, split in that order), ``g = B * z``, ``c_t = sum_k w_k g_{t - K +
    1 + k}`` a channel (a causal depthwise conv of ``conv_L_cache`` taps,
    ``ops/ssd.py::causal_conv``), ``out_proj(C * c)``. Two multiplicative
    gates round the conv, no activation and no bias.

    The layer's whole cache is ``{"conv": [B, conv_L_cache - 1, hidden]}``,
    the last rows of ``g`` before the conv, whatever the row's length; no K,
    no V, no state: a span (prefill) or one token starts from the stored
    rows, a pass without a cache from zeros. ``token_mask`` marks real
    tokens: a padded position feeds nothing into the window, so a left-padded
    row reaches its first real token with zeros to its left."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, u, cache=None, token_mask=None):
        from trlx_tpu.ops.ssd import causal_conv

        cfg = self.config
        B, T, E = u.shape
        bcz = _dense(cfg, 3 * E, False, ("embed", "ssm"), "in_proj")(u)
        conv_w = self.param("conv_weight", param_with_axes(_conv_taps_init, ("conv", "ssm")), (cfg.conv_L_cache, E), cfg.param_dtype)
        with jax.named_scope("trlx/short_conv"):
            b_gate, c_gate, z = jnp.split(bcz, 3, axis=-1)
            g = b_gate * z
            if token_mask is not None:
                g = g * token_mask.reshape(B, T, 1).astype(g.dtype)
            c, conv_state = causal_conv(g, conv_w, None, None if cache is None else cache["conv"])
            y = c_gate * c
        out = _dense(cfg, E, False, ("ssm", "embed"), "out_proj")(y)
        return out, (None if cache is None else {"conv": conv_state.astype(cache["conv"].dtype)})


@functools.lru_cache(maxsize=None)
def _warn_indivisible_experts(num_experts: int, axis: int) -> None:
    """Warn ONCE per (experts, axis) pair: the divisibility fit silently
    drops the expert axis, so expert-parallel dispatch degrades to replicated
    compute — a throughput cliff that deserves a diagnosis line (same
    contract as ``pipeline.py::pick_microbatches``). lru_cache keeps it to
    one line instead of one per layer per trace per recompile."""
    from trlx_tpu.utils import logging

    logging.get_logger(__name__).warning(
        "num_experts %d not divisible by mesh expert axis %d: expert-parallel "
        "dispatch runs replicated — resize the expert axis or the expert "
        "count to recover EP",
        num_experts, axis,
    )


def _maybe_expert_mesh():
    """The traced mesh, iff its ``expert`` axis actually partitions experts
    (size > 1)."""
    mesh = _traced_global_mesh()
    if mesh is not None and mesh.shape.get("expert", 1) > 1:
        return mesh
    return None


@jax.custom_vjp
def permute_rows(rows: jax.Array, perm: jax.Array, inv: jax.Array) -> jax.Array:
    """``rows[perm]`` for a permutation ``perm`` of the rows and its inverse
    ``inv``. The transpose of a permutation is its inverse: the backward is
    this function again, ``g[inv]``, bit for bit what JAX's own transpose of
    the gather (a scatter-add into zeros at unique rows) gives. On a v5e
    that scatter of a dropless layer's sorted row buffer ran at 5.5 GB/s
    where the gather of the same buffer runs at 192 (``bf16[40960,7680]``:
    113.36 ms against 3.28; PERF.md, PR 44)."""
    return rows.at[perm].get(unique_indices=True)


permute_rows.defvjp(
    lambda rows, perm, inv: (permute_rows(rows, perm, inv), (perm, inv)),
    lambda res, g: (permute_rows(g, res[1], res[0]), None, None),
)


@jax.custom_vjp
def rows_or_zero(rows: jax.Array, slot: jax.Array, live: jax.Array) -> jax.Array:
    """``rows[slot]`` for ``rows [C, d]`` and ``slot [M]`` in ``[0, C]``,
    where slot ``C`` reads a row of zeros; ``live [C]`` says which entry of
    ``slot`` reads each row (``slot[live[r]] == r``, and no other entry
    does). So the transpose is a gather too, ``g[live]``: what the
    scatter-add JAX would derive gives, bit for bit, without the scatter
    (``permute_rows``)."""
    padded = jnp.concatenate([rows, jnp.zeros((1,) + rows.shape[1:], rows.dtype)])
    return padded.at[slot].get(mode="promise_in_bounds")


rows_or_zero.defvjp(
    lambda rows, slot, live: (rows_or_zero(rows, slot, live), live),
    lambda live, g: (g.at[live].get(mode="promise_in_bounds", unique_indices=True), None, None),
)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def rows_of_tokens(x: jax.Array, live: jax.Array, slot: jax.Array, K: int) -> jax.Array:
    """``x[live // K]``: the token of each of the first ``C`` sorted
    assignments (``live [C]``: sorted row -> assignment ``token * K +
    choice``; ``slot [N * K]``: assignment -> sorted row, ``C`` for one past
    them). A token is read by up to ``K`` rows, so the transpose adds: each
    token sums the gradients of its ``K`` assignments' rows, gathered by
    ``slot`` with zeros for the assignments past ``C``: the sum
    ``jnp.repeat``'s own transpose makes of a ``[N * K, d]`` row buffer, by a
    gather and no scatter."""
    return x.at[live // K].get(mode="promise_in_bounds")


def _rows_of_tokens_bwd(K, res, g):
    live, slot = res
    like = jax.ShapeDtypeStruct((slot.shape[0] // K,) + g.shape[1:], g.dtype)
    (dx,) = jax.linear_transpose(lambda x: jnp.repeat(x, K, axis=0), like)(rows_or_zero(g, slot, live))
    return dx, None, None


rows_of_tokens.defvjp(
    lambda x, live, slot, K: (rows_of_tokens(x, live, slot, K), (live, slot)), _rows_of_tokens_bwd
)


def _either(take_first, first, second, *operands):
    """``first(*operands)`` or ``second(*operands)`` on the chip's own
    reading of ``take_first`` (the one ``lax.cond`` of the dropless
    dispatch, forward and backward)."""
    return jax.lax.cond(take_first, first, second, *operands)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def one_of_two_rows(bodies, take_first, x, gate_vals, kernels, route):
    """``bodies[0]`` or ``bodies[1]`` of ``(x, gate_vals, kernels, route)``,
    two statements of one function, by ``take_first``. Differentiated as
    one: the backward reads ``take_first`` again and differentiates the body
    taken from the INPUTS, inside its own branch. Left to JAX, the forward
    ``cond`` would return both bodies' residuals, and the body not taken has
    to fill its own with zeros: ``tokens x K``-row buffers written for
    nothing, the cost the compact body is there to take away."""
    return _either(take_first, *bodies, x, gate_vals, kernels, route)


def _one_of_two_rows_bwd(bodies, res, g):
    take_first, *inputs = res

    def back(body):
        def run(x, gate_vals, kernels, route, g):
            _, vjp = jax.vjp(lambda *diff: body(*diff, route), x, gate_vals, kernels)
            return vjp(g)

        return run

    dx, dgates, dkernels = _either(take_first, back(bodies[0]), back(bodies[1]), *inputs, g)
    return None, dx, dgates, dkernels, None


one_of_two_rows.defvjp(
    lambda bodies, take_first, *inputs: (one_of_two_rows(bodies, take_first, *inputs), (take_first, *inputs)),
    _one_of_two_rows_bwd,
)


def _window(x, route, bound, w):
    """Window ``w`` of a sort: the assignments ``live [bound]`` of the sorted
    rows ``[w x bound, (w + 1) x bound)``, their tokens' rows of ``x [N, d]``
    (a token is read by up to ``K`` rows: a gather, no ``repeat``) and the
    part of each held group that lies among them."""
    _, order, unsort, group_sizes = route
    lo = w * bound
    if order.shape[0] % bound:
        # whole windows: the last one reads assignment 0 where it passes the
        # end of the sort, past every group like the rows before it
        order = jnp.pad(order, (0, -order.shape[0] % bound))
    live = jax.lax.dynamic_slice_in_dim(order, lo, bound)
    xin = x.at[live // (unsort.shape[0] // x.shape[0])].get(mode="promise_in_bounds")
    ends = jnp.cumsum(group_sizes)
    in_window = lambda edge: jnp.clip(edge, lo, lo + bound)
    return live, xin, in_window(ends) - in_window(ends - group_sizes)


def _rows_by_window(rows: int, bound: int, d: int, dtype) -> jax.Array:
    """Room for every window of a sort of ``rows`` rows, ``bound`` rows
    each, and nothing written: a window's rows land where they are computed,
    and only rows that were written are ever used (``_sum_live_rows``)."""
    return jax.lax.empty((-(-rows // bound) * bound, d), dtype)


def _sum_live_rows(by_window: jax.Array, unsort: jax.Array, held_rows: jax.Array, K: int, gates=None) -> jax.Array:
    """``[N, d]`` float32: the sum of each token's ``K`` live sorted rows
    (``unsort [N x K]``: assignment ``token * K + choice`` -> sorted row),
    each times its gate where ``gates [N, K]`` are given. ONE gather reads the
    rows choice-major, by the ``K`` columns of ``unsort`` viewed ``[N, K]``
    laid end to end (the index array is transposed, 4 bytes an assignment,
    never the rows), so choice ``k`` is the whole-tile slice ``[k x N, (k +
    1) x N)`` of what it wrote; each product is taken and added in float32
    in the order ``k = 0..K-1``, as ``_sum_choices``' einsum does. Nothing
    has the shape ``[N, K, d]``: a ``[N x K, d]`` buffer viewed that way is a
    view at ``K = 8`` alone, where a token's rows fill an (8, 128) tile; at 6
    and 4 the chip copies the buffer (2.91 ms for ``f32[16384,6,2560]``, 72 +
    128 times a cycle of cell 6; PR 59's trace, PR 62). ``K`` gathers of
    ``[N, d]``, one a column, were 31 MB more code in cell 6 (``hbm_code_gib``
    0.262 -> 0.293, +0.67% of ``peak_hbm_gib``; builder, PR 62)."""
    N = unsort.shape[0] // K
    by_choice = unsort.reshape(N, K).T
    rows = by_window.at[by_choice.reshape(K * N)].get(mode="promise_in_bounds")
    # an assignment that is not among the first ``held_rows`` of the sort
    # (another chip's expert, padding) reads what no window wrote: a select
    # over the gather drops it. (All of them reading ONE row instead was
    # slower in every cell it was tried in, 0.2 to 1.2% of the rate; PR 59.)
    total = None
    for k in range(K):
        term = jnp.where((by_choice[k] < held_rows)[:, None], jax.lax.slice_in_dim(rows, k * N, (k + 1) * N), 0)
        term = term.astype(jnp.float32)
        if gates is not None:
            term = term * gates[:, k, None].astype(jnp.float32)
        total = term if total is None else total + term
    return total


def _choice_gates(gate_vals: jax.Array, real: jax.Array, K: int, dtype) -> jax.Array:
    """``[N, K]`` gates as ``_sum_choices`` forms them: zero for a padding
    token, rounded to the rows' dtype."""
    return (gate_vals.reshape(-1, K) * real[:, None]).astype(dtype)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def held_rows(sorted_rows, bound, x, gate_vals, kernels, route):
    """``MoEMLP._all_rows`` with its row buffers cut to windows of ``bound``
    sorted rows: ONE ``while`` over the windows that hold a live row
    (``ceil(held rows / bound)`` of them, a traced count; one wherever the
    router is less than twice as fond of these experts as of the others)
    gathers a window's tokens, runs ``sorted_rows`` on them and writes the
    ``[bound, d]`` result where the window stands in an uninitialised
    ``[tokens x K, d]`` buffer; one gather then reads every assignment's
    row choice-major and each token sums its ``K``, or zeros, under their
    gates (``_sum_live_rows``: ``_sum_choices``' products in its order, with
    no ``[tokens, K, d]`` view of the buffer). Nothing is summed over
    windows, so the value is ``_all_rows``' to the bit however many windows
    ran.

    The backward is one ``while`` over the same windows, from the INPUTS:
    it runs a window's ``sorted_rows`` again, hands it the gradient of its
    rows (``g`` of each row's token times the row's gate: one gather of
    ``bound`` rows of ``g [tokens, d]``, as the window gathers its tokens),
    writes the gradient of the window's tokens' rows where the window stands
    in the ONE ``[tokens x K, d]`` room the backward holds, reduces ``result x
    g`` over ``d`` to the gradient of the window's gates (``[bound]`` float32,
    written where the window stands in a ``[tokens x K]`` vector), and adds
    the kernels' gradients into its carry: the one sum over windows, exact
    for one window. After the loop each assignment reads its gate's gradient
    and each token sums its ``K`` rows' gradients in float32
    (``_sum_live_rows`` without gates). A kernel nothing is differentiated by
    (a frozen leaf reaches the layer through ``stop_gradient``) has a
    gradient that is only ever added to itself in that carry, and the
    compiler takes the carry and the grouped matmul that feeds it out (read
    in the compiled train steps, PR 59). A ``while`` of a traced count has no
    reverse mode of JAX's own, and a bounded ``scan`` in its place would keep
    every window's residuals."""
    real, _, unsort, group_sizes = route
    N, d = x.shape
    K = unsort.shape[0] // N
    held = jnp.sum(group_sizes)

    def body(w, by_window):
        _, xin, sizes = _window(x, route, bound, w)
        return jax.lax.dynamic_update_slice_in_dim(by_window, sorted_rows(xin, kernels, sizes), w * bound, 0)

    by_window = jax.lax.fori_loop(0, -(-held // bound), body, _rows_by_window(N * K, bound, d, x.dtype))
    return _sum_live_rows(by_window, unsort, held, K, _choice_gates(gate_vals, real, K, x.dtype))


def _held_rows_bwd(sorted_rows, bound, res, g):
    x, gate_vals, kernels, route = res
    real, _, unsort, group_sizes = route
    N, d = x.shape
    K = unsort.shape[0] // N
    held = jnp.sum(group_sizes)
    gates_of = partial(_choice_gates, real=real, K=K, dtype=x.dtype)
    gates = gates_of(gate_vals).reshape(N * K)

    def body(w, carry):
        d_xins, d_gates, d_kernels = carry
        live, xin, sizes = _window(x, route, bound, w)
        out, vjp = jax.vjp(lambda xin, kernels: sorted_rows(xin, kernels, sizes), xin, kernels)
        # bilinear in the rows and the gates: each gradient needs the other alone
        g_rows = g.at[live // K].get(mode="promise_in_bounds")
        gate_rows = gates.at[live].get(mode="promise_in_bounds")
        d_xin, d_kernel = vjp((g_rows * gate_rows[:, None].astype(g.dtype)).astype(out.dtype))
        d_gate = jnp.sum(out.astype(g.dtype) * g_rows, axis=-1)
        write = lambda by_window, rows: jax.lax.dynamic_update_slice_in_dim(by_window, rows, w * bound, 0)
        return write(d_xins, d_xin), write(d_gates, d_gate), jax.tree_util.tree_map(jnp.add, d_kernels, d_kernel)

    rows = _rows_by_window(N * K, bound, d, x.dtype)
    d_xins, d_gates, d_kernels = jax.lax.fori_loop(
        0, -(-held // bound), body,
        (rows, jax.lax.empty(rows.shape[:1], g.dtype), jax.tree_util.tree_map(jnp.zeros_like, kernels)),
    )
    d_gates = jnp.where(unsort < held, d_gates.at[unsort].get(mode="promise_in_bounds"), 0)
    d_gates = d_gates.reshape(N, K).astype(x.dtype)
    (d_gates,) = jax.linear_transpose(gates_of, gate_vals)(d_gates)
    dx = _sum_live_rows(d_xins, unsort, held, K).astype(x.dtype)
    return dx, d_gates, d_kernels, None


held_rows.defvjp(lambda *args: (held_rows(*args), args[2:]), _held_rows_bwd)


class MoEMLP(nn.Module):
    """Mixture-of-experts MLP: top-k router, then one of two dispatches.

    TPU-first design (the reference has no MoE at all — SURVEY.md §2.3 lists
    EP as n/a; this is a beyond-parity capability for the mixtral, olmoe and
    smallthinker families). The router runs in fp32 over ``num_experts``
    logits, on ``router_input`` where the ``Block`` hands one in (a family
    whose router reads the block's input) and on ``x`` otherwise. Experts are
    gated (``act(gate) * up``) where ``moe_gated`` says so, whatever the
    activation. Expert weights carry a leading dim of the experts HELD:
    ``num_experts``, or under dropless routing a contiguous slice
    ``[moe_first_expert, moe_first_expert + moe_experts_held)`` of them, one
    chip's share of a deployment (below). Returns ``(y, aux)`` where ``aux``
    is the layer's additive statistics (``aux_size``), summed over layers /
    microbatches / pipeline stages and normalized by ``router_aux_summary`` /
    ``router_load_summary``.

    ``moe_capacity_factor > 0`` — GShard-style einsum dispatch:

    - each sequence is a dispatch group: tokens route to their top-k experts
      with a *static* per-group capacity ``C = ceil(k·T·cf/E)`` (first
      choices claim slots before second choices; overflow tokens fall back to
      the residual path). No sorting, no dynamic gather.
    - the ``E`` dim shards over the mesh's ``expert`` axis; the
      dispatch/combine einsums change token layout from batch-sharded to
      expert-sharded and back, which GSPMD lowers to all_to_all over the
      ``expert`` axis (the EP analogue of Megatron TP's allreduce).
      Per-expert matmul dims still shard over ``fsdp``/``model``.
    - at decode (T = 1) the capacity is ``max(1, ceil(k·cf/E)) ≥ 1`` and
      top-k indices are distinct, so decode never drops tokens.

    ``moe_capacity_factor == 0`` — dropless token-choice routing: the
    ``B·T·k`` (token, expert) assignments are sorted by expert and the three
    expert matmuls run as grouped matmuls over the sorted rows, whose FLOPs
    are those of the assignments, not of ``E`` times them
    (``ops/grouped_matmul.py``: ``jax.lax.ragged_dot``, which the TPU
    compiler makes a Mosaic kernel with 512-row tiles and GSPMD can place
    under a mesh; where groups average under 256 rows on one TPU device, a
    decode step's groups of about 8 rows, the megablox Pallas kernel with a
    128-row tile instead).
    Shapes are static (``B·T·k`` rows whatever the routing); nothing couples
    two tokens, so a row's output does not depend on its neighbours. The sort
    and the unsort are permutations of the ``[B·T·k, d]`` row buffer
    (``permute_rows``), so the backward moves it by two gathers too, each by
    the other's index; no scatter of rows is in the layer (transposed by JAX,
    the two gathers were two scatter-adds that a v5e ran at 5.5 GB/s against
    the gathers' 192: 227 ms of a 548 ms train step at hidden 7680).

    **Held experts** (``moe_experts_held`` below ``num_experts``): the router
    and the top-k run over all ``num_experts``, the gates are renormalised
    over all ``k`` chosen, and an assignment to an expert that lives on
    another chip sorts past the last held group exactly as a padding row
    does, is never computed and adds nothing: the layer returns the part of
    the result its own experts give, and that partial result goes on to the
    next layer. Nothing stands in for the absent chips or their traffic.
    The held experts' assignments sort FIRST, so such a layer builds its row
    buffers at ``held_row_bound`` rows, twice the share an even router sends
    here, and not at ``B·T·k`` (``_held_rows``): the tokens of the first
    ``bound`` sorted assignments are gathered (no ``repeat``), the three
    grouped matmuls, the activation and the selects run on ``bound`` rows,
    and the weighted sum reads each assignment's result through a gather
    that gives a row of zeros to the assignments past the bound
    (``rows_or_zero``, ``rows_of_tokens``: a gather in the backward too). A
    call whose held rows pass the bound runs every row (``_all_rows``, the
    body of a layer that holds them all) behind one ``lax.cond`` on the
    traced count, forward and backward (``one_of_two_rows``): dropless and
    exact either way, the same bits from both bodies. That second body is
    code the chip holds, affordable where the bound cuts the rows by eight
    or more (at most a sixteenth of the experts held). A layer that holds a
    larger share (less than half) has ONE body instead (``held_rows``): the
    experts on a WINDOW of ``bound`` sorted rows, in a ``while`` over the
    windows that hold a live row (``ceil(held rows / bound)`` trips on the
    traced count, one unless a call overflows the bound), each window's rows
    written where the window stands in an uninitialised ``[B·T·k, d]``
    buffer; after the loop one gather reads each assignment's row, choice
    by choice, and each token sums its ``k``, or zeros, under their gates in
    ``_all_rows``' order, once (no ``[B·T, k, d]`` view of the buffer, a
    copy on the chip unless ``k`` is 8): the bits of the layer that holds
    every expert however many windows ran. Its
    backward is one ``while`` over the same windows from the layer's inputs.
    ``aux`` counts the calls that fitted the bound (``moe/compact_frac``).
    Where no bound applies (every expert held, or half of them or more; a
    short call, a decode step's: ``held_row_bound``) there is one body and
    no ``cond`` or ``while``.
    """

    config: TransformerConfig

    @nn.compact
    def __call__(
        self, x: jax.Array, token_mask: Optional[jax.Array] = None,
        router_input: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        cfg = self.config
        E, K = cfg.num_experts, cfg.num_experts_per_tok
        held = cfg.experts_held
        B, T, d = x.shape
        f = cfg.expert_width
        if held < E and cfg.moe_capacity_factor != 0:
            raise NotImplementedError(
                "moe_experts_held below num_experts runs under dropless routing "
                "(moe_capacity_factor=0) only: the capacity dispatch holds every expert"
            )

        logits = nn.Dense(
            E,
            use_bias=False,
            dtype=jnp.float32,
            param_dtype=cfg.param_dtype,
            kernel_init=param_with_axes(nn.initializers.normal(0.02), ("embed", "expert_sel")),
            name="router",
        )((x if router_input is None else router_input).astype(jnp.float32))  # [B, T, E]
        if cfg.moe_scoring == "sigmoid":
            # each logit scored on its own; the balance statistic below reads
            # the scores as a share of their sum
            scores = jax.nn.sigmoid(logits)
            probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
        else:
            scores = probs = jax.nn.softmax(logits, axis=-1)
        if cfg.moe_topk_method == "noaux_tc":
            # the bias decides WHICH experts, never how much: the gates are
            # the chosen experts' scores without it
            bias = self.param("router_bias", param_with_axes(nn.initializers.normal(cfg.moe_bias_init_std), ("expert_sel",)), (E,), cfg.param_dtype)
            _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), K)
            gate_vals = jnp.take_along_axis(scores, idx, axis=-1)
        else:
            gate_vals, idx = jax.lax.top_k(scores, K)  # [B, T, K]
        chosen_scores = gate_vals
        if cfg.moe_renormalize and cfg.moe_renormalize_eps:
            gate_vals = gate_vals / (jnp.sum(gate_vals, axis=-1, keepdims=True) + cfg.moe_renormalize_eps)
        elif cfg.moe_renormalize:
            gate_vals = gate_vals / jnp.maximum(
                jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
            )
        if cfg.routed_scaling_factor != 1.0:
            gate_vals = gate_vals * cfg.routed_scaling_factor
        # padding tokens route nowhere: they reach no expert and leave the
        # layer with zero output (the Block residual carries them)
        w = (
            jnp.ones((B, T), jnp.float32)
            if token_mask is None
            else token_mask.reshape(B, T).astype(jnp.float32)
        )

        def expert_kernel(name, shape, axes):
            return self.param(
                name,
                param_with_axes(nn.initializers.normal(0.02), axes),
                shape,
                cfg.param_dtype,
            ).astype(cfg.dtype)

        kernels = {}
        if cfg.moe_gated:
            kernels["w_gate"] = expert_kernel("w_gate", (held, d, f), ("expert", "embed", "ffn"))
        kernels["w_up"] = expert_kernel("w_up", (held, d, f), ("expert", "embed", "ffn"))
        kernels["w_down"] = expert_kernel("w_down", (held, f, d), ("expert", "ffn", "embed"))

        compact = None  # [calls that took the compact path, calls] of a layer that holds a share
        if cfg.moe_capacity_factor == 0:
            y, counts, dropped, compact = self._dropless(x, w, gate_vals, idx, kernels)
        else:
            y, counts, dropped = self._capacity(x, w, gate_vals, idx, kernels)
        if cfg.num_shared_experts:
            # every token, unweighted; a padding token's part is dropped with
            # the rest of its output
            shared = MLP(cfg, cfg.moe_shared_expert_intermediate_size or cfg.num_shared_experts * f, name="shared_expert")(x)
            y = y + shared.astype(y.dtype) * w[..., None].astype(y.dtype)

        # Switch load-balance loss over the assignments asked for: E·Σ f_e·p_e
        # (1.0 when both routing fractions and router probs are uniform).
        # Means are over REAL tokens only — padding must not train the router.
        # Everything is returned as token-weighted sufficient statistics so
        # that accumulation over layers / microbatches / pipeline stages
        # stays correctly weighted under uneven padding.
        n_real = jnp.sum(w)
        denom = jnp.maximum(n_real, 1.0)
        me = jnp.sum(probs * w[..., None], axis=(0, 1)) / denom
        ce = counts / (denom * K)
        aux_lb = E * jnp.sum(me * ce)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)  # [B, T]
        z_sum = jnp.sum((lse**2) * w)
        busiest = E * jnp.max(ce)  # the busiest expert's tokens over the mean
        stats = [aux_lb * n_real, z_sum, n_real, dropped, n_real * K, busiest * n_real]
        if held < E:
            # the real assignments that fell on a held expert, and the
            # busiest held expert over the mean of the held
            here = counts[cfg.moe_first_expert : cfg.moe_first_expert + held]
            stats += [jnp.sum(here), held * jnp.max(here) / jnp.maximum(jnp.sum(here), 1.0) * n_real]
            # dropless calls that fitted ``held_row_bound``, and dropless calls
            stats += [compact[0], compact[1]]
        if cfg.num_shared_experts:
            # rows through the shared expert, and the sum of the chosen raw scores
            stats += [n_real * cfg.num_shared_experts, jnp.sum(chosen_scores * w[..., None])]
        aux = jnp.stack(stats)
        return y.astype(cfg.dtype), aux

    def _experts(self, kernels, matmul, xin):
        """gate/up/down on ``xin`` with ``matmul(rows, kernel)``."""
        act = get_activation(self.config.activation)
        if "w_gate" not in kernels:  # two matrices an expert; its own scope, so that a trace tells it from the gated form's
            with jax.named_scope(f"trlx/{self.config.activation}_experts"):
                h = act(matmul(xin, kernels["w_up"]))
            return matmul(h, kernels["w_down"])
        h = act(matmul(xin, kernels["w_gate"]))
        up = matmul(xin, kernels["w_up"])
        return matmul(h * up, kernels["w_down"])

    def _dropless(self, x, w, gate_vals, idx, kernels):
        """``_dropless_rows`` on all the tokens at once or, past
        ``MOE_MAX_TOKENS`` of them, on pieces of at most ``MOE_PIECE_TOKENS``
        one after another: the sorted ``[tokens·K, d]`` row buffers are what a
        long prefill cannot hold (``moe_token_pieces``)."""
        B, T, d = x.shape
        pieces = moe_token_pieces(B * T, self.config.num_experts_per_tok * d * x.dtype.itemsize)
        if pieces == 1:
            return self._dropless_rows(x, w, gate_vals, idx, kernels)
        split = lambda a: a.reshape(pieces, 1, B * T // pieces, *a.shape[2:])
        y, counts, dropped, compact = jax.lax.map(
            lambda piece: self._dropless_rows(*piece, kernels),
            tuple(split(a) for a in (x, w, gate_vals, idx)),
        )
        return y.reshape(B, T, d), counts.sum(0), dropped.sum(0), None if compact is None else compact.sum(0)

    def _dropless_rows(self, x, w, gate_vals, idx, kernels):
        """Every real token through all of its ``K`` experts that are held
        here. Returns ``(y [B, T, d], assignments asked for per router expert
        [E], dropped = 0, [took the compact path, 1] or None where every
        sorted row may be live)``."""
        cfg = self.config
        E, K = cfg.num_experts, cfg.num_experts_per_tok
        B, T, d = x.shape
        N = B * T
        if _maybe_expert_mesh() is not None:
            raise ValueError(
                "moe_capacity_factor=0 (dropless routing) runs the experts on "
                "one device; this mesh has an `expert` axis above 1 — set "
                "moe_capacity_factor > 0 for expert-parallel dispatch"
            )
        real = w.reshape(N) > 0
        # assignment r = token r // K, choice r % K; padding sorts past the
        # last expert, outside every group, and is never computed
        expert = jnp.where(real[:, None], idx.reshape(N, K), E).reshape(N * K)
        counts = jnp.zeros((E + 1,), jnp.int32).at[expert].add(1)[:E]
        group_sizes = counts
        held, first = cfg.experts_held, cfg.moe_first_expert
        if held < E:
            # an assignment to an expert of another chip joins the padding
            # past the last held group; the groups are the held experts'
            expert = jnp.where((expert >= first) & (expert < first + held), expert - first, held)
            group_sizes = counts[first : first + held]
        order = jnp.argsort(expert)  # stable: sorted row -> assignment
        unsort = jnp.zeros_like(order).at[order].set(jnp.arange(N * K), unique_indices=True)
        route = (real, order, unsort, group_sizes)
        bound = held_row_bound(N * K, held, E)
        if bound == N * K:  # every sorted row may be live: one program, no choice to count
            y = self._all_rows(x, gate_vals, kernels, route)
            compact = None if held == E else jnp.zeros((2,), jnp.float32)
        else:
            # the held experts' assignments sort first, so the live rows are
            # the first ``sum(group_sizes)`` of ``order``: where they fit the
            # bound, the row buffers have ``bound`` rows
            fits = jnp.sum(group_sizes) <= bound
            if bound * MOE_HELD_MIN_CUT <= N * K:
                # a small share: a call that overflows the bound runs every
                # row, as a layer that holds them all does, behind one cond
                bodies = (partial(self._held_rows, bound=bound), self._all_rows)
                y = one_of_two_rows(bodies, fits, x, gate_vals, kernels, route)
            else:
                # a large share: one body, window after window of ``bound`` rows
                y = held_rows(self._sorted_rows, bound, x.reshape(N, d), gate_vals, kernels, route)
            compact = jnp.stack([fits.astype(jnp.float32), jnp.ones((), jnp.float32)])
        return y.reshape(B, T, d), counts.astype(jnp.float32), jnp.zeros((), jnp.float32), compact

    def _sorted_rows(self, xin, kernels, group_sizes):
        """The experts on the first rows of a sort: ``xin [rows, d]``, of
        which the first ``sum(group_sizes)`` lie in a group. Zeros past them."""
        from trlx_tpu.ops.grouped_matmul import grouped_matmul

        # rows past the last group (padding, an expert of another chip) hold
        # whatever the kernels left, and so does their GRADIENT: neither
        # kernel's backward writes it (on a v5e ``ragged_dot`` left NaN there
        # over memory that held NaN, PERF.md, PR 40). The select is the value
        # it was given either way (the compiler folds it away) and passes the
        # gradient of the rows in a group alone, once, where it would reach
        # the tokens; the rows in between never mix with a group's
        in_a_group = (jnp.arange(xin.shape[0]) < jnp.sum(group_sizes))[:, None]
        xin = jnp.where(in_a_group, xin, jax.lax.stop_gradient(xin))

        out = self._experts(  # sorted by expert
            kernels, lambda lhs, kernel: grouped_matmul(lhs, kernel, group_sizes), xin
        )
        return jnp.where(in_a_group, out, 0)

    @staticmethod
    def _sum_choices(out, gate_vals, real):
        """``[N, K, d]`` results by assignment, weighed and summed a token:
        ``[N, d]`` float32."""
        N, K, _ = out.shape
        gates = gate_vals.reshape(N, K) * real[:, None]
        return jnp.einsum(
            "nkd,nk->nd", out, gates.astype(out.dtype),
            preferred_element_type=jnp.float32,
        )

    def _all_rows(self, x, gate_vals, kernels, route):
        """All ``tokens x K`` sorted rows through the grouped matmuls,
        whichever of them lie in a group. ``[N, d]`` float32."""
        real, order, unsort, group_sizes = route
        K, d = self.config.num_experts_per_tok, x.shape[-1]
        N = real.shape[0]
        # a permutation of the K-fold repeated rows, so that its backward is
        # the gather by ``unsort`` (``permute_rows``), as the backward of the
        # unsort below is the gather by ``order``: no scatter of the row
        # buffer, which a v5e runs at 5.5 GB/s against a gather's 192.
        # x[order // K] has no inverse to gather by: its transpose
        # scatter-adds with duplicates
        xin = permute_rows(jnp.repeat(x.reshape(N, d), K, axis=0), order, unsort)
        out = self._sorted_rows(xin, kernels, group_sizes)  # [N·K, d]
        out = permute_rows(out, unsort, order).reshape(N, K, d)
        return self._sum_choices(out, gate_vals, real)

    def _held_rows(self, x, gate_vals, kernels, route, bound):
        """``_all_rows`` for a call whose live rows, the first
        ``sum(group_sizes)`` of the sort, number at most ``bound``: the row
        buffers hold the first ``bound`` sorted rows and nothing is built at
        ``tokens x K`` rows but the gathered results the weighted sum reads
        (forward) and the gathered row gradients the tokens sum (backward).
        Equal to ``_all_rows`` to the bit, value and gradients: the same
        rows through the same kernels, zeros where its select writes zeros,
        the same sum over a token's ``K`` choices."""
        real, order, unsort, group_sizes = route
        K, d = self.config.num_experts_per_tok, x.shape[-1]
        N = real.shape[0]
        live = order[:bound]  # sorted row -> assignment, the held experts' first
        slot = jnp.minimum(unsort, bound)  # assignment -> sorted row, or the row of zeros
        xin = rows_of_tokens(x.reshape(N, d), live, slot, K)
        out = self._sorted_rows(xin, kernels, group_sizes)  # [bound, d]
        out = rows_or_zero(out, slot, live).reshape(N, K, d)
        return self._sum_choices(out, gate_vals, real)

    def _capacity(self, x, w, gate_vals, idx, kernels):
        """GShard one-hot dispatch with a static capacity. Returns ``(y,
        assignments asked for per expert [E], assignments dropped)``."""
        cfg = self.config
        E, K = cfg.num_experts, cfg.num_experts_per_tok
        B, T, d = x.shape
        # dispatch groups: capacity (and the [.., E, C] dispatch tensors)
        # scale with the group size G, not with T — whole-sequence groups
        # would make the slot tensors O(T²) per row at long context. G is
        # the largest divisor of T ≤ moe_group_size (static).
        G = T
        if cfg.moe_group_size > 0:
            G = min(cfg.moe_group_size, T)
            while T % G:
                G -= 1
        N = B * (T // G)
        xg = x.reshape(N, G, d)

        C = max(1, int(np.ceil(K * G * cfg.moe_capacity_factor / E)))
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32).reshape(N, G, K, E)
        # padding tokens claim no capacity slots
        onehot = onehot * w.reshape(N, G)[..., None, None].astype(jnp.int32)
        # slot assignment with choice-priority: every token's first choice
        # outranks any second choice (GShard top-2 semantics)
        perm = onehot.transpose(0, 2, 1, 3).reshape(N, K * G, E)
        pos = jnp.cumsum(perm, axis=1) - perm  # slots taken before this entry
        kept = perm * (pos < C)
        slot = jax.nn.one_hot(pos, C, dtype=jnp.float32) * kept[..., None]
        gates_perm = (
            gate_vals.reshape(N, G, K).transpose(0, 2, 1).reshape(N, K * G)
        )
        combine = (
            (slot * gates_perm[..., None, None]).reshape(N, K, G, E, C).sum(1)
        )  # [N, G, E, C] fp32
        dispatch = slot.reshape(N, K, G, E, C).sum(1)

        mesh = _maybe_expert_mesh()

        if mesh is not None and E % mesh.shape.get("expert", 1):
            _warn_indivisible_experts(E, mesh.shape.get("expert", 1))

        def expert_sharded(a):
            from trlx_tpu.parallel.sharding import constrain_activation

            return constrain_activation(a, mesh, "expert", ("data", "fsdp"))

        xin = jnp.einsum("ngd,ngec->encd", xg, dispatch.astype(x.dtype))
        xin = expert_sharded(xin)  # ← GSPMD inserts the dispatch all_to_all
        out = self._experts(
            kernels, lambda a, kernel: jnp.einsum("enca,eab->encb", a, kernel), xin
        )
        out = expert_sharded(out)
        y = jnp.einsum("encd,ngec->ngd", out, combine.astype(out.dtype))
        counts = jnp.sum(onehot.astype(jnp.float32), axis=(0, 1, 2))
        dropped = jnp.sum((perm - kept).astype(jnp.float32))
        return y.reshape(B, T, d), counts, dropped


# A dropless layer sorts its (token, expert) assignments into [tokens·K, d] row
# buffers (the rows in, the rows out, the gathered copy): 15 KB a token a
# buffer at hidden 2560 and six experts a token, 3 GB a buffer for the 98,304
# tokens of a 16-row prefill at 6144, which one v5e cannot hold beside the
# weights (compiled for a described v5e, PR 33: 12.8 GiB for that generate
# program). Tokens do not interact, so past MOE_MAX_TOKENS the layer runs
# pieces of at most MOE_PIECE_TOKENS one after another. The largest forward of
# the cells that came before (64 rows x 640 = 40,960 tokens) is under the
# first number and keeps its program. Constants with their arithmetic, not
# settings.
MOE_MAX_TOKENS = 65536
MOE_PIECE_TOKENS = 16384
# The same buffers at hidden 7680 and eight experts a token are 123 KB a
# token: 5.0 GB a buffer for those 40,960 tokens, under the first number and
# far over the chip. So a forward under MOE_MAX_TOKENS whose row buffer would
# pass MOE_MAX_ROW_BYTES is cut too, into pieces of at most
# MOE_PIECE_ROW_BYTES a buffer. The largest buffer of the cells that came
# before (OLMoE: 40,960 x 8 x 2048 x 2 = 1.34 GB) is under the first number
# and keeps its program.
MOE_MAX_ROW_BYTES = 2 * 2**30
MOE_PIECE_ROW_BYTES = 2**29
# Where a layer holds `held` of its `E` experts, an even router sends
# tokens·K·held/E of a call's tokens·K assignments to them, and they sort
# first. The row buffers of such a call have MOE_HELD_ROWS_FACTOR times that
# share of the rows, up to the grouped matmul's row tile (`held_row_bound`):
# 6.25% of tokens·K where a chip holds 8 of 256, 12.5% at 8 of 128, 25% at 32
# of 256, 50% at 16 of 64. A call whose held rows pass the bound (a router
# twice as fond of this chip's experts as of the others) is still dropless
# and exact, by one of two forms, chosen by the cut alone:
# - a cut of MOE_HELD_MIN_CUT or more (at most a sixteenth of the experts
#   held): the `bound`-row body or, for the call that overflows, all tokens·K
#   rows, behind one `cond` (`one_of_two_rows`, PR 57). The second body is
#   code the chip holds, 1.0 to 1.7 MB a forward layer and 3.1 to 4.1 MB a
#   trained one (compiled for a described v5e, PR 57): +0.2 to +0.5% of
#   `peak_hbm_gib` where the cut is 8 to 16, and 0.93 and 1.27% where a chip
#   holds 32 of 256 and 16 of 64, against a bound of 1%;
# - a smaller cut: ONE body, the experts on a window of `bound` sorted rows,
#   in a `while` over the windows that hold a live row (`held_rows`, PR 59:
#   ceil(held rows / bound) trips, one in every call seen), each window's
#   rows written where the window stands in an uninitialised `[tokens·K, d]`
#   buffer and read once after the loop: one gather, choice-major, whose K
#   whole slices of `[tokens, d]` are summed under their gates in float32
#   (PR 62: a `[tokens, K, d]` view of the buffer is free at K = 8 alone and
#   a copy at 6 and 4). The backward's loop carries ONE such room (each row's
#   gradient), a `[tokens·K]` float32 vector (each gate's gradient, reduced
#   over `d` in the window) and the kernels' gradients; after it every token
#   sums its K rows' gradients the same way. No second body, so no code growth
#   (+9 and +7 MB over the parent's programs at 32 of 256 and 16 of 64), and
#   +5.8 and +4.7% `samples_per_s` there (builder, PR 59). At a cut of 8 to 16
#   the same form LOST 2.5 and 3.6% to the two bodies (cells 7, 8: the
#   `while`'s fixed passes over `[tokens·K, d]` rooms beside a body of 6% of
#   the rows), and a form that summed the windows' float32 `[tokens, d]`
#   results in the loop's carry lost 0.5 to 1.3% there, which is why both
#   forms stay.
# A call under MOE_HELD_MIN_ROWS rows keeps them all: a decode step's 256 to
# 1024 rows are a few MB, and the `cond` around them cost more than the
# passes it spared (cell 9's loop of rounds 9.57 -> 9.85 s, cell 7's 5.42 ->
# 5.44 s, PR 57; a `while` there is not priced). The observable is the share
# held and the call's size; constants with their arithmetic, not settings.
MOE_HELD_ROWS_FACTOR = 2
MOE_HELD_MIN_CUT = 8
MOE_HELD_MIN_ROWS = 4096


def held_row_bound(rows: int, held: int, experts: int) -> int:
    """Rows of the sorted row buffers of a dropless call of ``rows = tokens x
    K`` assignments in a layer that holds ``held`` of ``experts``; ``rows``
    where no bound applies: a short call, or a share whose bound would reach
    the rows (half the experts or more, every expert)."""
    from trlx_tpu.ops import grouped_matmul

    tile = grouped_matmul.ROW_TILE
    even = -(-rows * held // experts)
    bound = -(-MOE_HELD_ROWS_FACTOR * even // tile) * tile
    return bound if rows >= MOE_HELD_MIN_ROWS and bound < rows else rows


def moe_token_pieces(tokens: int, token_bytes: int = 0) -> int:
    """How many equal pieces a dropless layer cuts ``tokens`` into: 1 up to
    ``MOE_MAX_TOKENS`` tokens whose sorted row buffer (``token_bytes`` a
    token: experts a token x hidden x item size) stays under
    ``MOE_MAX_ROW_BYTES``, else the fewest that divide ``tokens`` into pieces
    of at most ``MOE_PIECE_TOKENS`` and ``MOE_PIECE_ROW_BYTES`` (1 again
    where nothing divides it)."""
    most = MOE_PIECE_TOKENS
    if tokens <= MOE_MAX_TOKENS:
        if tokens * token_bytes <= MOE_MAX_ROW_BYTES:
            return 1
        most = max(MOE_PIECE_ROW_BYTES // token_bytes, 1)
    fewest = -(-tokens // most)
    return next((n for n in range(fewest, 64 * fewest) if tokens % n == 0), 1)


def aux_size(cfg: TransformerConfig) -> int:
    """Length of a Block's aux statistics, all additive: [lb·tokens, Σ
    tokens·lse², tokens, assignments dropped, assignments asked for, (busiest
    expert / mean)·tokens] and, where the layer holds a share of its experts,
    [assignments that fell on a held expert, (busiest held expert / mean of
    the held)·tokens, dropless calls whose held rows fitted ``held_row_bound``,
    dropless calls that had such a bound]; then, where the layers have shared experts, [rows
    through the shared expert, Σ chosen raw router scores]; then, where
    layers run ``KDAMixer``, [Σ beta, real tokens, one slot a KDA layer that
    only it writes] (``kda_summary``); then, under a headwise gate, [Σ of the
    real tokens' mean gate, real tokens] (``gate_summary``)."""
    kda = kda_layers(cfg)
    return _moe_aux_size(cfg) + (2 + len(kda) if kda else 0) + 2 * bool(cfg.attention_gate_type)


def gate_summary(aux: jax.Array) -> jax.Array:
    """The mean headwise gate of a pass's gated layers over their real tokens
    and heads (``attention_gate_type``; the last two slots of ``aux``): near 0
    a stand-in gate shuts attention out of the stream, near 1 it is no gate."""
    return aux[-2] / jnp.maximum(aux[-1], 1.0)


def _moe_aux_size(cfg: TransformerConfig) -> int:
    return 6 + 4 * _holds_share(cfg) + 2 * bool(cfg.num_shared_experts)


def kda_layers(cfg: TransformerConfig) -> Tuple[int, ...]:
    """The layers that run ``KDAMixer``."""
    return tuple(i for i in range(cfg.num_layers) if cfg.mixer_layout and cfg.mixer_layout[i] == "kda")


def kda_summary(aux: jax.Array, cfg: TransformerConfig) -> jax.Array:
    """``[log_decay_min, beta_mean]`` of a pass's KDA layers: the most
    negative log decay accumulated inside any chunk of any of them (what the
    chunked delta rule's exponents must survive: ``ops/delta_rule.py``), and
    the mean write strength over their real tokens and heads."""
    m = _moe_aux_size(cfg)
    return jnp.stack([jnp.min(aux[m + 2 :]), aux[m] / jnp.maximum(aux[m + 1], 1.0)])


def _holds_share(cfg: TransformerConfig) -> bool:
    return 0 < cfg.experts_held < cfg.num_experts


def router_aux_summary(aux: jax.Array) -> jax.Array:
    """Accumulated per-layer aux statistics → ``[load_balance, router_z]``
    (token-weighted means; exact for the z-loss under any layer/microbatch/
    pipeline-stage accumulation, token-weighted for the balance loss — which
    is a product of per-group means and therefore has per-group semantics,
    like every microbatched MoE implementation)."""
    return aux[:2] / jnp.maximum(aux[2], 1.0)


def router_load_summary(aux: jax.Array, cfg: TransformerConfig) -> jax.Array:
    """Accumulated per-layer aux statistics → ``[dropped_frac,
    load_max_over_mean]``: the share of (token, expert) assignments asked for
    and not computed (0 under dropless routing, always), and the busiest
    expert's assignments over the mean, token-weighted over the layers.
    Where the layers hold a share of their experts, also ``[held_frac,
    held_load_max_over_mean, compact_frac]``: the share of the assignments asked for that
    fell on a held expert (every one of them computed), the busiest held
    expert over the mean of the held, and ``compact_frac``: the share of the
    dropless calls with a bound on their row buffers (``held_row_bound``)
    whose held rows fitted it (the others ran every row: as exact, slower)."""
    load = [aux[3] / jnp.maximum(aux[4], 1.0), aux[5] / jnp.maximum(aux[2], 1.0)]
    if _holds_share(cfg):  # [held_frac, held_load_max_over_mean, compact_frac]
        load += [aux[6] / jnp.maximum(aux[4], 1.0), aux[7] / jnp.maximum(aux[2], 1.0), aux[8] / jnp.maximum(aux[9], 1.0)]
    return jnp.stack(load)


def shared_expert_summary(aux: jax.Array, cfg: TransformerConfig) -> jax.Array:
    """``[shared_row_frac, chosen_score_mean]`` of layers with shared experts:
    the share of the expert rows computed here that are the shared expert's
    (its rows over its rows plus the routed assignments that fell on an
    expert held here), and the mean raw router score of a chosen expert."""
    i = 6 + 4 * _holds_share(cfg)
    routed_here = aux[6] if _holds_share(cfg) else aux[4] - aux[3]
    return jnp.stack([aux[i] / jnp.maximum(aux[i] + routed_here, 1.0), aux[i + 1] / jnp.maximum(aux[4], 1.0)])


def _cache_is_paged(cache) -> bool:
    """True when ``cache`` carries a block table (``paged_kv.attach_block_
    table``): a per-layer list of dicts, or the scanned stacked dict."""
    if cache is None:
        return False
    if isinstance(cache, dict):
        return "block_table" in cache
    if isinstance(cache, list):
        return any(
            isinstance(layer, dict) and "block_table" in layer
            for layer in cache
        )
    return False


def _needs_token_mask(cfg: TransformerConfig) -> bool:
    return cfg.num_experts > 0 or cfg.mixer != "none" or cfg.mixer_layout is not None


def _query_slots(q_offset, B: int, T: int) -> jax.Array:
    """[B, T] slot indices of queries at ``q_offset`` (scalar, or [B] when
    rows sit at different cache depths — speculative decoding)."""
    off = jnp.asarray(q_offset)
    if off.ndim == 1:
        off = off[:, None]
    return jnp.broadcast_to(off + jnp.arange(T)[None, :], (B, T))


def _token_validity(slot_mask: jax.Array, q_offset, T: int) -> jax.Array:
    """[B, T] validity of the query tokens occupying cache slots
    ``[q_offset, q_offset + T)`` of a [B, S] slot mask."""
    qs = _query_slots(q_offset, slot_mask.shape[0], T)
    return jax.vmap(lambda m, q: m[q])(slot_mask, qs)


class Block(nn.Module):
    config: TransformerConfig
    layer: int = 0  # which entry of the config's per-layer layout this block reads

    @nn.compact
    def __call__(self, x, attention_bias, positions, cache=None, cache_index=None, flash_args=None, token_mask=None, kv_extents=None, selection=None):
        """``(x, new_cache, aux, selection)``: ``selection`` is the set of
        keys in force behind this layer (``LatentAttention``), which the next
        layer attends over if its indexer type is ``shared``; None wherever no
        selection binds."""
        cfg = self.config
        layout = cfg.layer_layout(self.layer)
        rotary, sparse = layout.rotary, layout.ffn == "moe"
        # a router that reads the block's raw input, before the input norm
        router_input = x if sparse and cfg.moe_router_input == "block_input" else None

        def run_mlp(h):
            if sparse:
                return MoEMLP(cfg, name="mlp")(h, token_mask, router_input)
            return MLP(cfg, name="mlp")(h), jnp.zeros((_moe_aux_size(cfg),), jnp.float32)

        # (a layer without a mixer has no norm in front of one: nemotron_h's `E`)
        h = Norm(cfg, name="ln_attn")(x) if layout.mixer != "none" else None
        if cfg.mixer == "mamba2":
            # both mixers read the same normed input; their outputs are
            # summed before the one residual add, then the MLP as usual
            attn_in = h * cfg.attention_in_multiplier if cfg.attention_in_multiplier != 1.0 else h
            attn_out, new_cache = Attention(cfg, rotary, name="attn")(attn_in, attention_bias, positions, cache, cache_index, flash_args, kv_extents)
            mix_out, new_state = Mamba2Mixer(cfg, name="mixer")(h, cache, token_mask)
            if cache is not None:
                new_cache = {**new_cache, **new_state}
            x = x + mix_out * cfg.ssm_out_multiplier + attn_out * cfg.attention_out_multiplier
            mlp_out, aux = run_mlp(Norm(cfg, name="ln_mlp")(x))
            return x + mlp_out, new_cache, aux, None
        kda_stats = gate_stats = None
        if layout.mixer == "none":
            attn_out, new_cache = None, cache  # nothing cached: the layer's empty dict goes back as it came
        elif layout.mixer == "mamba2":  # the layer's WHOLE mixer, under the name Falcon-H1's blocks give theirs
            attn_out, new_cache = Mamba2Mixer(cfg, name="mixer")(h, cache, token_mask)
        elif layout.mixer == "lightning":
            attn_out, new_cache = LightningMixer(cfg, name="attn")(h, positions, cache, token_mask)
        elif layout.mixer == "kda":
            attn_out, new_cache, kda_stats = KDAMixer(cfg, name="attn")(h, cache, token_mask)
        elif layout.mixer == "conv":
            attn_out, new_cache = ShortConvMixer(cfg, name="attn")(h, cache, token_mask)
        elif cfg.latent_attention:
            lends = self.layer + 1 < cfg.num_layers and cfg.layer_layout(self.layer + 1).indexer == "shared"
            attn_out, new_cache, selection, gate_stats = LatentAttention(cfg, self.layer, lends, name="attn")(
                h, attention_bias, positions, cache, cache_index, flash_args, kv_extents, selection, token_mask
            )
        else:
            attn_out, new_cache = Attention(cfg, rotary, name="attn")(h, attention_bias, positions, cache, cache_index, flash_args, kv_extents)
        if cfg.sandwich_norm:
            attn_out = Norm(cfg, name="ln_attn_post")(attn_out)
        if "none" in (layout.mixer, layout.ffn):
            # a layer of ONE sublayer: one norm, one sublayer, one residual add, under the names the
            # two-sublayer block gives that half
            if layout.mixer == "none":
                out, aux = run_mlp(Norm(cfg, name="ln_mlp")(x))
            else:
                out, aux = attn_out, jnp.zeros((_moe_aux_size(cfg),), jnp.float32)
            x = x + (out * cfg.residual_multiplier if cfg.residual_multiplier != 1.0 else out)
        elif cfg.parallel_residual:
            mlp_in = h if cfg.shared_ln else Norm(cfg, name="ln_mlp")(x)
            mlp_out, aux = run_mlp(mlp_in)
            x = x + attn_out + mlp_out
        else:
            scaled = cfg.residual_multiplier != 1.0
            x = x + (attn_out * cfg.residual_multiplier if scaled else attn_out)
            h = Norm(cfg, name="ln_mlp")(x)
            mlp_out, aux = run_mlp(h)
            if cfg.sandwich_norm:
                mlp_out = Norm(cfg, name="ln_mlp_post")(mlp_out)
            x = x + (mlp_out * cfg.residual_multiplier if scaled else mlp_out)
        kda = kda_layers(cfg)
        if kda:  # [sum of beta, real tokens, one slot a KDA layer for its most negative chunk decay] behind the experts' statistics
            own = jnp.zeros((2 + len(kda),), jnp.float32)
            if kda_stats is not None:
                own = own.at[:2].set(kda_stats[:2]).at[2 + kda.index(self.layer)].set(kda_stats[2])
            aux = jnp.concatenate([aux, own])
        if cfg.attention_gate_type:  # [sum of the real tokens' mean gate, real tokens], last (`gate_summary`)
            aux = jnp.concatenate([aux, jnp.zeros((2,), jnp.float32) if gate_stats is None else gate_stats])
        return x, new_cache, aux, selection


class NextTokenModule(nn.Module):
    """One next-token-prediction module (``TransformerConfig.mtp_layers``):
    ``x' = eh_proj [h_norm(h) ; e_norm(emb)]``, one ``Block`` of the kind
    ``layer_layout(layer)`` over its own keys and values, and a final norm of
    its own. Returns the block's output (what a second module would be fed),
    that output normed (what the main head reads) and the cache layer."""

    config: TransformerConfig
    layer: int

    @nn.compact
    def __call__(self, hidden, emb, attention_bias, positions, cache, cache_index, flash_args, token_mask):
        cfg = self.config
        joined = jnp.concatenate([Norm(cfg, "h_norm")(hidden), Norm(cfg, "e_norm")(emb)], axis=-1)
        x = _dense(cfg, cfg.hidden_size, False, ("mlp", "embed"), "eh_proj")(joined)
        # PPO neither reads nor trains the module: it takes no adapter
        block = Block(dataclasses.replace(cfg, lora_r=0), self.layer, name="block")
        x, new_cache, _, _ = block(x, attention_bias, positions, cache, cache_index, flash_args, token_mask)
        return x, Norm(cfg, "ln_f")(x), new_cache


def _remat_policy(cfg: TransformerConfig):
    """Rematerialisation policy per ``cfg.remat``:

    - ``full``: save nothing — recompute the whole block in the backward
      (max memory saving, ~1/3 extra FLOPs; NeMo's ``activations_checkpoint
      _granularity: full``, ``megatron_20b.yaml:77-79``);
    - ``minimal``: save matmul outputs with batch dims (the MXU-expensive
      results), recompute cheap elementwise/norm ops only — NeMo's
      ``selective`` granularity.
    """
    if cfg.remat == "minimal":
        return jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    return None  # full: save nothing


def _block_cls(cfg: TransformerConfig):
    if cfg.remat in ("full", "minimal"):
        return nn.remat(Block, policy=_remat_policy(cfg))
    return Block


class _ScanBlockBody(nn.Module):
    """``nn.scan`` body: one Block step over the layer axis.

    Carry = (hidden states, branch-input buffer). ``branch_at`` is the layer
    index whose *input* activations feed the hydra reference branch (−1 =
    never); captured via ``where`` since scan has no data-dependent exits.
    """

    config: TransformerConfig

    @nn.compact
    def __call__(self, carry, cache_layer, layer_idx, attention_bias, positions, cache_index, flash_args, branch_at, token_mask, kv_extents):
        x, branch_input, aux_sum = carry
        # one body for every layer: a stack whose layers all select for
        # themselves runs here, one that lends a selection is a mixed layout
        x_new, new_cache, aux, _ = _block_cls(self.config)(self.config, name="block")(
            x, attention_bias, positions, cache_layer, cache_index, flash_args, token_mask, kv_extents
        )
        if branch_input is not None:  # static: only hydra passes pay for it
            branch_input = jnp.where(layer_idx == branch_at, x, branch_input)
        return (x_new, branch_input, aux_sum + aux), new_cache


class CausalTransformer(nn.Module):
    """Decoder-only LM. Methods:

    - ``__call__``: full forward → logits (+ final hidden, + intermediate
      hidden at ``branch_layer`` for the hydra reference branch, + updated
      cache during decode).
    - ``forward_branch``: run the top layers from ``branch_layer`` on given
      hidden states (the frozen-reference replay; reference hydra semantics,
      ``trlx/models/modeling_ppo.py:331-427``).
    """

    config: TransformerConfig

    def setup(self):
        cfg = self.config
        self.wte = nn.Embed(
            cfg.vocab_size,
            cfg.hidden_size,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            embedding_init=param_with_axes(nn.initializers.normal(cfg.embed_init_std), ("vocab", "embed")),
            name="wte",
        )
        if cfg.position_scheme == "learned":
            self.wpe = nn.Embed(
                cfg.max_position_embeddings + cfg.pos_offset,
                cfg.hidden_size,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                embedding_init=param_with_axes(nn.initializers.normal(0.02), ("seq", "embed")),
                name="wpe",
            )
        if cfg.embedding_layernorm:
            self.emb_ln = Norm(cfg, name="emb_ln")
        if cfg.scan_layers and cfg.mixed_layout:
            raise NotImplementedError(
                "scan_layers (and the pipeline schedule, which needs it) runs ONE Block "
                f"body over stacked parameters; model_type {cfg.model_type!r} has layers of "
                f"more than one attention layout, indexer type or feed-forward kind ({sorted(set(cfg.layer_layouts), key=str)}), "
                "and its carry has no place for a selection of keys that one layer hands the next: run it "
                "with scan_layers=False (ROADMAP.md queue 2, B3: a scan over whole periods; B8: the selection in its carry)"
            )
        if cfg.scan_layers:
            # roll all blocks into one lax.scan over stacked params — one
            # traced/compiled block instead of L, O(1) compile time and
            # program size in depth (the 20B+ scale path; the reference
            # leans on NeMo/Megatron for this regime,
            # ``trlx/models/modeling_nemo_ilql.py:253+``)
            scan_cls = nn.scan(
                _ScanBlockBody,
                variable_axes={"params": 0},
                split_rngs={"params": True},
                in_axes=(0, 0) + (nn.broadcast,) * 7,
                out_axes=0,
                length=cfg.num_layers,
            )
            self.scan_blocks = scan_cls(cfg, name="h_scan")
            self.blocks = []
        else:
            block = _block_cls(cfg)
            self.blocks = [block(cfg, i, name=f"h_{i}") for i in range(cfg.num_layers)]
        if cfg.final_norm:
            self.ln_f = Norm(cfg, name="ln_f")
        if not cfg.tie_word_embeddings:
            self.lm_head = _dense(cfg, cfg.vocab_size, cfg.lm_head_bias, ("embed", "vocab"), "lm_head")
        self.mtp = [NextTokenModule(cfg, cfg.num_layers + k, name=f"mtp_{k}") for k in range(cfg.mtp_layers)]

    def _logits(self, h):
        cfg = self.config
        logits = self.wte.attend(h) if cfg.tie_word_embeddings else self.lm_head(h)
        return logits * cfg.lm_head_multiplier if cfg.lm_head_multiplier != 1.0 else logits

    def _embed(self, input_ids, positions):
        cfg = self.config
        x = _activation_sharded(self.wte(input_ids))
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
        if cfg.position_scheme == "learned":
            x = x + self.wpe(positions + cfg.pos_offset)
        if cfg.embedding_layernorm:
            x = self.emb_ln(x)
        return x

    def _attention_bias(self, key_mask, query_slots, query_positions, window):
        """Additive [B, 1, T, S] bias over key *slots*: slot-causal + padding
        (+ ALiBi on true token positions).

        Slots are laid out in input order (prompt slots first, generated slots
        after), so slot-causality ``key_slot <= query_slot`` IS temporal
        causality, for full passes (slots ≡ positions), cache prefill, and
        single-token decode alike. ``key_mask`` [B, S] marks written, non-pad
        slots; positions of key slots are recovered as ``cumsum(mask)-1``
        (left-padded prompts thus attend with correct relative distances).
        """
        cfg = self.config
        S = key_mask.shape[1]
        key_slots = jnp.arange(S)[None, None, :]  # [1, 1, S]
        visible = (key_slots <= query_slots[:, :, None]) & (key_mask[:, None, :] > 0)
        if window:
            # slot distance ≡ position distance (padding is left-only)
            visible = visible & (
                query_slots[:, :, None] - key_slots < window
            )
        bias = jnp.where(visible[:, None, :, :], 0.0, -1e9)
        if cfg.position_scheme == "alibi":
            slopes = jnp.asarray(alibi_slopes(cfg.num_heads), dtype=jnp.float32)
            key_pos = jnp.maximum(jnp.cumsum(key_mask, axis=1) - 1, 0)  # [B, S]
            dist = (key_pos[:, None, :] - query_positions[:, :, None]).astype(jnp.float32)
            alibi = slopes[None, :, None, None] * dist[:, None, :, :]
            bias = bias + jnp.where(visible[:, None, :, :], alibi, 0.0)
        return bias

    def _attn_inputs(
        self, key_mask, positions, q_offset, use_flash, window
    ) -> Tuple[Optional[jax.Array], Optional[Dict[str, Any]]]:
        """``(bias, flash_args)`` for one forward of the layers whose layout
        has this ``window`` (``LayerLayout.window``) — the single definition
        of the masking semantics, shared by the unpipelined path, the hydra
        branch replay, and each pipeline stage. Queries occupy slots
        ``[q_offset, q_offset + T)`` (0 for full passes)."""
        if use_flash:
            return None, self._flash_args(key_mask, positions, window, q_offset=q_offset)
        B, T = positions.shape
        query_slots = _query_slots(q_offset, B, T)
        return self._attention_bias(key_mask, query_slots, positions, window), None

    def _layer_plans(self, layers, cache, key_mask, positions, cache_index, use_flash, extents):
        """``(bias, flash_args, cache view)`` for each of ``layers``, built
        once per kind of layer: layers of one window and one cache length
        share theirs, so a uniform stack builds ONE, as it always has.

        A layer whose cache is shorter than the row's ``S`` slots is a window
        layer's ring (``make_kv_cache``). A single-token step reads the ring
        under a bias over its ``C`` slots: ring position ``j`` holds the
        newest slot ``<= cache_index`` congruent to ``j``, valid where that
        slot is (``slot distance = position distance``, so every slot in the
        ring is inside the window and a padded one stays masked). A span from
        slot 0 (the sampler's prefill) attends over its own keys and leaves
        its last ``C`` positions in the ring."""
        cfg = self.config
        S = key_mask.shape[1]
        dense = cache is not None and not _cache_is_paged(cache)
        q_offset = cache_index if cache is not None and cache_index is not None else 0
        plans: Dict[Any, Any] = {}
        out = []
        latent_prefill = dense and cfg.latent_attention and positions.shape[1] > 1
        for i in layers:
            window = cfg.layer_layout(i).window
            slots = S
            if dense:  # (a layer with no slots at all, a recurrent state alone, reads the row's plan and none of it)
                slots = cache_slots(cache if isinstance(cache, dict) else cache[i]) or S
            if (window, slots) not in plans:
                if latent_prefill:  # a plan a KIND of latent layer: its window, and whether its cache is a ring
                    plans[window, slots] = self._latent_prefill_plan(key_mask, positions, cache_index, use_flash, window, slots)
                elif slots == S:
                    plans[window, slots] = self._attn_inputs(key_mask, positions, q_offset, use_flash, window) + (extents,)
                else:
                    plans[window, slots] = self._ring_plan(key_mask, positions, cache_index, use_flash, window, slots, extents)
            out.append(plans[window, slots])
        return out

    def _latent_prefill_plan(self, key_mask, positions, cache_index, use_flash, window, slots):
        """A span of tokens into a latent cache (the sampler's prefill)
        attends over its own keys, expanded, inside the layer's ``window``:
        per-head K and V of the whole cache are never built, so it must start
        at slot 0, as a ring's; a window layer whose cache has fewer ``slots``
        than the row leaves its last ``slots`` latents there (the view says
        ``ring``: ``LatentAttention``)."""
        ci = jnp.asarray(cache_index)
        if ci.ndim or (not isinstance(ci, jax.core.Tracer) and int(ci) != 0):
            raise NotImplementedError(
                "a span of tokens into a latent cache must start at slot 0, one scalar cache_index "
                "for all rows (the sampler's prefill): chunked prefill over a latent cache is not built"
            )
        T = positions.shape[1]
        view = StaticExtents((slots,), ring=True) if slots < key_mask.shape[1] else None
        return self._attn_inputs(key_mask[:, :T], positions, 0, use_flash, window) + (view,)

    def _ring_plan(self, key_mask, positions, cache_index, use_flash, window, slots, extents):
        cfg = self.config
        B, T = positions.shape
        ci = jnp.asarray(cache_index)
        if cfg.position_scheme == "alibi":
            raise NotImplementedError(
                "a window layer's ring cache (fewer slots than the row: make_kv_cache) "
                "takes no ALiBi (ROADMAP.md queue 2, B3)"
            )
        if ci.ndim:
            # each row's span of T tokens at its own slot (speculation's verify): the ring
            # is read after all T are written, so position j holds the newest slot <= the
            # span's last congruent to j, and a query sees those at or before its own slot
            # and inside its window. The span's first query still needs slot ci - window + 1,
            # which the write of the span's last must not have landed on
            if slots < window + T - 1:
                raise NotImplementedError(
                    f"a span of {T} tokens at each row's own index into a ring cache of {slots} slots under a window "
                    f"of {window}: the ring must hold window + span - 1 slots (make_kv_cache gives a model that drafts "
                    "with its own module, mtp_layers, window + gamma; a separate draft model beside a ring is not built)"
                )
            top = (ci + T - 1)[:, None]  # [B, 1]
            slot = top - jnp.mod(top - jnp.arange(slots)[None, :], slots)  # [B, C]
            valid = (slot >= 0) & (jnp.take_along_axis(key_mask, jnp.maximum(slot, 0), axis=1) > 0)
            behind = _query_slots(ci, B, T)[:, :, None] - slot[:, None, :]  # [B, T, C] slots behind the query
            visible = valid[:, None, :] & (behind >= 0) & (behind < window)
            return jnp.where(visible, 0.0, -1e9)[:, None], None, StaticExtents((slots,), ring=True)
        if T > 1:
            # (a traced index cannot be looked at: the caller's word for it)
            if not isinstance(ci, jax.core.Tracer) and int(ci) != 0:
                raise NotImplementedError(
                    "a span of tokens into a ring cache at ONE cache_index for all rows must start at "
                    "slot 0 (the sampler's prefill): chunked prefill over a ring is not built"
                )
            view = StaticExtents((slots,), ring=True)
            return self._attn_inputs(key_mask[:, :T], positions, 0, use_flash, window) + (view,)
        j = jnp.arange(slots)
        slot = ci - jnp.mod(ci - j, slots)  # the newest slot <= ci at ring position j
        ring_mask = jnp.where(slot >= 0, jnp.take(key_mask, jnp.maximum(slot, 0), axis=1), 0)
        if slots > window:  # a drafting model's ring holds `gamma` slots more than the window
            ring_mask = jnp.where(ci - slot < window, ring_mask, 0)
        bias = jnp.where(ring_mask > 0, 0.0, -1e9)[:, None, None, :]
        from trlx_tpu.ops.sampling import layer_extents

        return bias, None, StaticExtents(layer_extents(extents.slots if extents else (), slots), ring=True)

    def _flash_args(self, key_mask, query_positions, window, q_offset=0) -> Dict[str, Any]:
        """Inputs for the pallas flash-attention path: same masking semantics
        as ``_attention_bias`` but resolved inside the kernel (no [B,1,T,S]
        bias tensor is ever materialised)."""
        cfg = self.config
        args: Dict[str, Any] = {"key_mask": key_mask, "q_offset": q_offset}
        if window:
            args["window"] = window
        if cfg.position_scheme == "alibi":
            args["alibi_slopes"] = jnp.asarray(alibi_slopes(cfg.num_heads), jnp.float32)
            args["q_positions"] = query_positions
            args["k_positions"] = jnp.maximum(jnp.cumsum(key_mask, axis=1) - 1, 0)
        return args

    def __call__(
        self,
        input_ids: jax.Array,  # [B, T]
        attention_mask: Optional[jax.Array] = None,  # [B, T] (or [B, S] in decode)
        positions: Optional[jax.Array] = None,  # [B, T]
        cache: Optional[List[Dict[str, jax.Array]]] = None,
        cache_index: Optional[jax.Array] = None,
        branch_layer: Optional[int] = None,
        logits_span: Optional[Tuple[int, int]] = None,  # static [a, b): lm-head
        # projection restricted to these positions — the vocab matmul is the
        # single biggest op in PPO scoring/training forwards and only the
        # response span is consumed there
        kv_extents: Optional[Tuple[int, ...]] = None,  # static, ascending, ending at
        # the cache's slots: a single-token step attends over the first that
        # holds its slot (extent_attention); one extent or None reads them all
    ) -> Dict[str, Any]:
        cfg = self.config
        B, T = input_ids.shape
        extents = StaticExtents(tuple(kv_extents)) if kv_extents and len(kv_extents) > 1 else None
        if attention_mask is None:
            attention_mask = jnp.ones((B, T), jnp.int32)
        if cache is None:
            # full pass: key slots are the input sequence itself
            if positions is None:
                positions = jnp.maximum(jnp.cumsum(attention_mask, axis=1) - 1, 0)
        else:
            # attention_mask is the [B, S] slot mask over the whole cache;
            # queries occupy slots [cache_index, cache_index + T)
            if positions is None:
                offset = cache_index if cache_index is not None else 0
                query_slots = _query_slots(offset, B, T)
                key_pos = jnp.maximum(jnp.cumsum(attention_mask, axis=1) - 1, 0)
                positions = jax.vmap(lambda kp, qs: kp[qs])(key_pos, query_slots)

        token_mask = None
        if _needs_token_mask(cfg):
            # MoE routing must know which query tokens are real: padding
            # claims no expert capacity and trains no router statistics; a
            # recurrent mixer must feed no padding into its state
            if cache is None:
                token_mask = attention_mask
            else:
                offset = cache_index if cache_index is not None else 0
                token_mask = _token_validity(attention_mask, offset, T)

        x = self._embed(input_ids, positions)
        if self.mtp and self.is_initializing():  # no forward but `draft` runs the module: make its leaves
            self.draft(x, input_ids)
        # flash kernels take a scalar slot offset; per-row cache depths
        # (speculative decoding) go through the bias path (T is tiny there).
        # Paged (block-table-carrying) caches always take the bias path too:
        # the in-place paged kernels consume the additive bias rows, and
        # their bit-parity reference is the dense einsum path.
        vector_ci = cache_index is not None and jnp.asarray(cache_index).ndim > 0
        paged_cache = _cache_is_paged(cache)
        use_flash = (
            cfg.resolved_attention_impl() == "pallas"
            and T > 1
            and not vector_ci
            and not paged_cache
        )
        pipe_mesh = None if self.is_initializing() else _maybe_pipeline_mesh(cfg)
        if pipe_mesh is not None:
            x, branch_input, new_cache, aux = self._pipelined_blocks(
                pipe_mesh, x, attention_mask, positions, use_flash,
                cache, cache_index, branch_layer, extents,
            )
            return self._epilogue(x, branch_input, new_cache, logits_span, aux)
        plans = self._layer_plans(
            range(1 if cfg.scan_layers else cfg.num_layers),
            cache, attention_mask, positions, cache_index, use_flash, extents,
        )

        branch_input = None
        aux = jnp.zeros((aux_size(cfg),), jnp.float32)
        if cfg.scan_layers:
            bias, flash_args, extents = plans[0]
            branch_at = cfg.num_layers - branch_layer if branch_layer is not None else -1
            branch_buf0 = jnp.zeros_like(x) if branch_layer is not None else None
            (x, branch_buf, aux), new_cache = self.scan_blocks(
                (x, branch_buf0, aux),
                cache,  # stacked {"k": [L,B,S,KV,D], "v": ...} or None
                jnp.arange(cfg.num_layers),
                bias,
                positions,
                cache_index,
                flash_args,
                jnp.asarray(branch_at),
                token_mask,
                extents,
            )
            if branch_layer is not None:
                branch_input = branch_buf
        else:
            new_cache = [] if cache is not None else None
            selection = None
            for i, block in enumerate(self.blocks):
                if branch_layer is not None and i == len(self.blocks) - branch_layer:
                    # a branch that starts at a layer which borrows its keys
                    # is handed them with the hidden states (forward_branch)
                    borrows = selection is not None and cfg.layer_layout(i).indexer == "shared"
                    branch_input = (x, selection) if borrows else x
                layer_cache = cache[i] if cache is not None else None
                bias, flash_args, view = plans[i]
                x, updated, aux_i, selection = block(x, bias, positions, layer_cache, cache_index, flash_args, token_mask, view, selection)
                aux = aux + aux_i
                if cache is not None:
                    new_cache.append(updated)
            if cache is not None:  # a next-token-prediction module's layers: `draft` writes them
                new_cache.extend(cache[cfg.num_layers :])

        return self._epilogue(x, branch_input, new_cache, logits_span, aux)

    def _epilogue(self, x, branch_input, new_cache, logits_span, aux=None):
        """Shared forward tail: final norm + (span-restricted) lm head."""
        cfg = self.config
        h = self.ln_f(x) if cfg.final_norm else x
        logits = self._logits(h if logits_span is None else h[:, logits_span[0] : logits_span[1]])
        out = {
            "logits": logits,
            "hidden_states": h,
            "pre_norm_hidden": x,
            "branch_input": branch_input,
            "cache": new_cache,
        }
        if cfg.num_experts > 0 and aux is not None:
            # token-weighted [load_balance, router_z] over all layers —
            # trainers add router_aux_coef/router_z_coef · these to the loss
            out["router_aux_loss"] = router_aux_summary(aux)
            out["router_load"] = router_load_summary(aux, cfg)
            if cfg.num_shared_experts:
                out["router_shared"] = shared_expert_summary(aux, cfg)
        if aux is not None and new_cache is None and kda_layers(cfg):  # a whole pass (the decode loop carries no statistics)
            out["kda_stats"] = kda_summary(aux, cfg)
        if aux is not None and new_cache is None and cfg.attention_gate_type:
            out["attn_gate_mean"] = gate_summary(aux)
        return out

    def _pipelined_blocks(
        self, mesh, x, attention_mask, positions, use_flash, cache, cache_index, branch_layer, kv_extents=None
    ):
        """Run the stacked blocks through the GPipe schedule over the mesh's
        ``pipe`` axis (``parallel/pipeline.py``) — the reference's Megatron
        pipeline engine (``modeling_nemo_ilql.py:426-442``), here one jitted
        program with compiler-inserted stage handoffs. Attention inputs
        (bias/flash args) are rebuilt per microbatch inside each stage, since
        different stages hold different microbatches at any tick."""
        cfg = self.config
        from trlx_tpu.parallel.pipeline import pick_microbatches, pipeline_blocks

        B = x.shape[0]
        num_stages = mesh.shape["pipe"]
        num_micro = pick_microbatches(B, num_stages, cfg.pipe_microbatches)
        branch_at = cfg.num_layers - branch_layer if branch_layer is not None else -1
        body_block = Block(cfg, parent=None)
        in_decode = cache is not None and cache_index is not None
        window = cfg.layer_layout(0).window  # one layout: scan_layers refuses a mixed one

        def make_attn_inputs(mask_mb, pos_mb, ci_mb):
            # ci_mb: this stage's microbatch slice of a [B]-vector
            # cache_index (speculative decoding), or the scalar/None given
            q_offset = ci_mb if in_decode else 0
            tm = None
            if _needs_token_mask(cfg):
                tm = (
                    _token_validity(mask_mb, q_offset, pos_mb.shape[1])
                    if in_decode
                    else mask_mb
                )
            return self._attn_inputs(mask_mb, pos_mb, q_offset, use_flash, window) + (pos_mb, tm)

        def apply_block(layer_params, h, attn_inputs, cache_layer, cidx):
            bias_mb, flash_mb, pos_mb, tm = attn_inputs
            return body_block.apply(
                {"params": layer_params}, h, bias_mb, pos_mb, cache_layer, cidx, flash_mb, tm, kv_extents
            )[:3]  # one layout a stack (scan_layers): no layer lends its selection

        if cfg.remat in ("full", "minimal"):
            apply_block = jax.checkpoint(apply_block, policy=_remat_policy(cfg))

        return pipeline_blocks(
            self.variables["params"]["h_scan"]["block"],
            x,
            attention_mask.astype(jnp.int32),
            positions,
            num_stages=num_stages,
            num_microbatches=num_micro,
            make_attn_inputs=make_attn_inputs,
            apply_block=apply_block,
            cache=cache,
            cache_index=cache_index,
            branch_at=branch_at,
            mesh=mesh,
            aux_init=jnp.zeros((aux_size(cfg),), jnp.float32),
        )

    def forward_branch(
        self,
        hidden_states: jax.Array,  # [B, T, E] activations entering the branch
        branch_layer: int,
        attention_mask: Optional[jax.Array] = None,
        positions: Optional[jax.Array] = None,
        logits_span: Optional[Tuple[int, int]] = None,
    ) -> Dict[str, Any]:
        """Run the top ``branch_layer`` blocks + final norm + lm head.

        Applied with *frozen reference params* this replays the hydra branch
        on trunk activations shared with the policy — the reference's
        second-model-free KL baseline (``modeling_ppo.py:394-427``).
        """
        cfg = self.config
        selection = None
        if isinstance(hidden_states, tuple):  # with the keys its first layer borrows (__call__)
            hidden_states, selection = hidden_states
        B, T, _ = hidden_states.shape
        if attention_mask is None:
            attention_mask = jnp.ones((B, T), jnp.int32)
        if positions is None:
            positions = jnp.maximum(jnp.cumsum(attention_mask, axis=1) - 1, 0)
        top = range(cfg.num_layers - branch_layer, cfg.num_layers)
        plans = self._layer_plans(
            top[:1] if cfg.scan_layers else top, None, attention_mask, positions, None,
            cfg.resolved_attention_impl() == "pallas" and T > 1, None,
        )
        x = hidden_states
        if cfg.scan_layers:
            bias, flash_args, _ = plans[0]
            # scan over the top `branch_layer` rows of the stacked params —
            # the bound tree holds either a pre-sliced branch snapshot
            # (builder.hydra_ref_params) or the full stack
            stacked = self.variables["params"]["h_scan"]["block"]
            n_avail = jax.tree_util.tree_leaves(stacked)[0].shape[0]
            sliced = jax.tree_util.tree_map(lambda p: p[n_avail - branch_layer :], stacked)
            # parent=None: a detached functional Block (not a submodule —
            # its params come from the scanned stack, not this scope)
            body_block = Block(cfg, parent=None)

            def body(h, layer_params):
                out, _, _, _ = body_block.apply(
                    {"params": layer_params}, h, bias, positions,
                    flash_args=flash_args, token_mask=attention_mask,
                )
                return out, None

            if cfg.remat in ("full", "minimal"):
                body = jax.checkpoint(body, policy=_remat_policy(cfg))
            x, _ = jax.lax.scan(body, x, sliced)
        else:
            for block, (bias, flash_args, _) in zip(self.blocks[len(self.blocks) - branch_layer :], plans):
                x, _, _, selection = block(x, bias, positions, flash_args=flash_args, token_mask=attention_mask, selection=selection)
        h = self.ln_f(x) if cfg.final_norm else x
        logits = self._logits(h if logits_span is None else h[:, logits_span[0] : logits_span[1]])
        return {"logits": logits, "hidden_states": h}

    def draft(
        self,
        hidden: jax.Array,  # [B, T, E]: `pre_norm_hidden` of the tokens at slots [cache_index, cache_index + T)
        next_ids: jax.Array,  # [B, T]: the token AFTER each of them
        attention_mask: Optional[jax.Array] = None,  # [B, T], or the [B, S] slot mask with a cache
        cache: Optional[List[Dict[str, jax.Array]]] = None,  # the modules' layers: make_kv_cache(...)[num_layers:]
        cache_index: Optional[jax.Array] = None,
        logits_span: Optional[Tuple[int, int]] = None,
    ) -> Dict[str, Any]:
        """The next-token-prediction module (``mtp_layers``): from the stack's
        hidden state at token ``t`` and the embedding of token ``t + 1``, logits
        over token ``t + 2``, through the main embedding and head. The module's
        entry for token ``t`` lives at ``t``'s slot of its own cache layer: the
        rollout sampler's drafter (``ops/speculative.py::module_drafter``)."""
        cfg = self.config
        B, T = next_ids.shape
        if attention_mask is None:
            attention_mask = jnp.ones((B, T), jnp.int32)
        q_offset = cache_index if cache is not None and cache_index is not None else 0
        key_pos = jnp.maximum(jnp.cumsum(attention_mask, axis=1) - 1, 0)
        positions = jax.vmap(lambda kp, qs: kp[qs])(key_pos, _query_slots(q_offset, B, T))
        token_mask = _token_validity(attention_mask, q_offset, T) if cache is not None else attention_mask
        use_flash = cfg.resolved_attention_impl() == "pallas" and T > 1 and jnp.asarray(q_offset).ndim == 0
        bias, flash_args = self._attn_inputs(attention_mask, positions, q_offset, use_flash, None)
        with jax.named_scope("trlx/mtp_draft"):
            x, new_cache = hidden, []
            for k, module in enumerate(self.mtp):
                x, h, updated = module(
                    x, self._embed(next_ids, positions), bias, positions, None if cache is None else cache[k], cache_index, flash_args, token_mask
                )
                new_cache.append(updated)
            logits = self._logits(h if logits_span is None else h[:, logits_span[0] : logits_span[1]])
        return {"logits": logits, "cache": new_cache if cache is not None else None}

    def project_logits(self, hidden: jax.Array) -> jax.Array:
        """Vocab projection of (already final-normed) hidden states — lets
        loss code stream chunks through the lm head instead of
        materializing the full ``[B, T, V]`` logits (``SFTConfig.
        chunked_loss``; the [B,T,V] tensor is the peak-memory item at
        BLOOM-scale vocabularies)."""
        return self._logits(hidden)

    def init_cache(self, batch_size: int, max_length: int, dtype=None) -> List[Dict[str, jax.Array]]:
        """Allocate an all-zeros KV cache pytree."""
        return make_kv_cache(self.config, batch_size, max_length, dtype)


def make_kv_cache(
    cfg: TransformerConfig, batch_size: int, max_length: int, dtype=None, lane_packed: bool = True
) -> Any:
    """All-zeros KV cache pytree for ``cfg`` (usable outside module ``apply``).

    Layout follows the block layout: a per-layer list of ``{"k", "v"}`` dicts,
    or one stacked dict with a leading layer dim when ``cfg.scan_layers``.
    A layer's ``k`` and ``v`` are ``[B, slots, KV / P, P * D]``: ``P =
    lane_heads(D, KV)`` KV heads side by side in one 128-lane row where ``D <
    128``, ``128 % D == 0`` and ``KV % P == 0`` (two at a head of 64, four at
    32), the row-major reshape of ``[B, slots, KV, D]``'s last two axes, so
    that a decode step writes one slot in place (``ops/cache_layout.py``);
    ``P = 1``, the plain ``[B, slots, KV, D]``, at a head of 128 or more and
    where ``lane_packed`` is False: a block pool's leaves ``[NB, bs, KV, D]``
    and the rows bound for one, which the paged kernels read
    (``ops/slot_refill.py``). ``Attention`` takes ``P`` from the leaf.
    They are as long as the layer's layout needs
    (``cfg.layer_layout``): ``max_length`` slots for a full-causal layer,
    ``min(max_length, window)`` for a window layer, which the sampler then
    writes as a ring (slot ``t`` at ``t mod window``: ``CausalTransformer.
    _ring_plan``); a model that drafts its own rollouts (``mtp_layers``) gets
    ``window + mtp_layers`` slots a ring and, behind the blocks' layers, one
    more cache layer for each module. A ``mixer: mamba2`` layer also holds ``ssm`` (the recurrent
    state, float32 whatever ``dtype``: hundreds of steps of ``S = aS + ...``
    drift in bf16) and ``conv`` (the conv's last ``K - 1`` input rows). A
    ``lightning`` layer (``mixer_layout``) holds ``state`` ``[B, heads, d, d]``
    float32 and nothing else; a ``kda`` layer ``state`` ``[B, heads, d, d]``
    float32 and ``conv`` ``[B, kda_conv - 1, 3 heads d]`` and nothing else; a ``conv`` layer ``conv`` ``[B, conv_L_cache - 1,
    hidden]`` and nothing else; a ``mamba2`` layer (``mixer_layout``: Mamba-2 as the layer's whole mixer) ``ssm`` and
    ``conv`` as above and nothing else; a layer without a mixer (``none``) an EMPTY dict; an attention layer under a block selection
    (``sparse_topk`` > 0) holds ``kbar`` ``[B, KV, max_length / sparse_stride,
    D]`` beside ``k`` and ``v``, the keys' mean-pool its decode steps score. A
    latent-attention layer (``kv_lora_rank`` > 0) holds ``ckv`` ``[B, slots,
    kv_lora_rank]`` and ``k_rope`` ``[B, slots, qk_rope_head_dim]`` IN PLACE
    of ``k`` and ``v``: 576 numbers a slot at the published widths where
    per-head K and V would be 40,960. Under a learned selection
    (``index_topk`` > 0) a layer holds the same numbers as ONE leaf ``latent``
    ``[B, slots, kv_lora_rank + qk_rope_head_dim]``, the normed latent in
    columns ``[0, kv_lora_rank)`` and the roped key after it: its decode steps
    gather chosen slots, and a gathered row costs the same whatever it holds
    (``LatentAttention``). A layer whose indexer type is ``full`` also holds
    ``k_index`` ``[B, slots, index_head_dim]``, the index keys its decode
    steps score; a ``shared`` layer holds none. A WINDOW layer of a latent
    stack holds ``ckv`` and ``k_rope`` at its own sizes
    (``cfg.attention_sizes``) over ``min(max_length, window)`` slots, a ring
    of latents where that is fewer than the row's, and no index keys.
    """
    dtype = dtype or cfg.dtype
    stacked = (cfg.num_layers,) if cfg.scan_layers else ()

    def layer(i: int):
        layout, sizes = cfg.layer_layout(i), cfg.attention_sizes(i)
        # a model that drafts (`mtp_layers`) verifies a span of gamma + 1 = mtp_layers + 1
        # tokens a round: the write of the span's last must not land on the slot the
        # span's first still reads (`_ring_plan`)
        slots = min(max_length, layout.window + cfg.mtp_layers) if layout.window else max_length
        if layout.mixer == "kda":
            # the layer's whole cache: the delta rule's state (key channels by value channels, float32 as `ssm`
            # is) and the last `kda_conv - 1` rows of [q~ | k~ | v~] before the convs; no K, V or latent
            # (ops/cache_layout.py::VOCABULARY: `conv` beside `state`)
            heads, d = cfg.kda_heads, cfg.kda_head_dim
            return {
                "state": jnp.zeros(stacked + (batch_size, heads, d, d), jnp.float32),
                "conv": jnp.zeros(stacked + (batch_size, cfg.kda_conv - 1, 3 * heads * d), dtype),
            }
        def zeros(shapes):
            return {name: jnp.zeros(stacked + shape, dt) for name, (shape, dt) in shapes.items()}

        # a Mamba-2 mixer's state: the recurrence's, float32, and the conv's last `mamba_conv - 1` input rows
        mamba = {
            "ssm": ((batch_size, cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state), jnp.float32),
            "conv": ((batch_size, cfg.mamba_conv - 1, cfg.mamba_conv_channels), dtype),
        }
        if layout.mixer == "none":
            return {}  # a layer without a sequence mixer keeps nothing a sequence
        if layout.mixer == "mamba2":
            return zeros(mamba)  # the layer's whole cache: no K or V beside them (ops/cache_layout.py::VOCABULARY)
        if layout.mixer == "conv":
            # the layer's whole cache: the last `conv_L_cache - 1` rows of the gated input `B * x` before
            # the conv, whatever the row's length; no K, V, latent or state (ops/cache_layout.py::VOCABULARY:
            # `conv` alone)
            return {"conv": jnp.zeros(stacked + (batch_size, cfg.conv_L_cache - 1, cfg.hidden_size), dtype)}
        if cfg.latent_attention:
            # the normed latent and the one roped key (ops/cache_layout.py::VOCABULARY), the
            # same slot axis and cache_index as K and V have, and no K or V; side by side
            # in one row a slot on a layer whose steps gather chosen slots. Two layouts
            # for a measured reason, not for anything a layer without an indexer needs:
            # with the one leaf there too, the TPU compiler's memory-space assignment
            # kept the caches on chip in place of q_b_proj's prefetched weights and
            # pangu718b_ppo_decode ran 2.7% slower (PERF.md section 6, PR 43). To merge
            # them, measure that cell (ROADMAP queue 2, B4c)
            r, dr = sizes.kv_lora_rank, sizes.rope
            if layout.indexer:
                latent = {"latent": jnp.zeros(stacked + (batch_size, slots, r + dr), dtype)}
            else:
                latent = {
                    "ckv": jnp.zeros(stacked + (batch_size, slots, r), dtype),
                    "k_rope": jnp.zeros(stacked + (batch_size, slots, dr), dtype),
                }
            if layout.indexer == "full":
                # the indexer's ONE normed, roped key a slot (ops/cache_layout.py::VOCABULARY),
                # on the layers that select for themselves only
                latent["k_index"] = jnp.zeros(stacked + (batch_size, slots, cfg.index_head_dim), dtype)
            return latent
        if layout.mixer == "lightning":
            # the layer's whole cache: the recurrence's state, float32 as `ssm` is, and no K or V
            # (ops/cache_layout.py::VOCABULARY)
            heads, d = cfg.lightning_heads, cfg.lightning_head_dim
            return {"state": jnp.zeros(stacked + (batch_size, heads, d, d), jnp.float32)}
        side = lane_heads(cfg.dims_per_head, cfg.kv_heads) if lane_packed else 1
        shapes = {
            "k": ((batch_size, slots, cfg.kv_heads // side, side * cfg.dims_per_head), dtype),
            "v": ((batch_size, slots, cfg.kv_heads // side, side * cfg.dims_per_head), dtype),
        }
        if cfg.sparse_topk:
            # the keys' running mean-pool (`pooled_keys`), by KV head: kernel j is complete once the
            # row's token `sparse_stride * j + sparse_kernel - 1` is in (ops/cache_layout.py::VOCABULARY)
            shapes["kbar"] = ((batch_size, cfg.kv_heads, max_length // cfg.sparse_stride, cfg.dims_per_head), dtype)
        if cfg.mixer == "mamba2":
            shapes.update(mamba)
        return zeros(shapes)

    if cfg.scan_layers:  # one layout for the stack: CausalTransformer refuses a mixed one
        return layer(0)
    # the blocks' layers, then one for each next-token-prediction module (`CausalTransformer.draft`)
    return [layer(i) for i in range(cfg.num_layers + cfg.mtp_layers)]


def stack_layer_params(backbone: Dict[str, Any], num_layers: int, prefix: str = "h_") -> Dict[str, Any]:
    """Per-layer ``h_i`` subtrees → one stacked ``h_scan/block`` subtree
    (leading layer dim). Converts HF-imported / unscanned param trees into the
    ``scan_layers`` layout."""
    out = {
        k: v
        for k, v in backbone.items()
        if not (k.startswith(prefix) and k[len(prefix) :].isdigit())
    }
    layers = [backbone[f"{prefix}{i}"] for i in range(num_layers)]
    out["h_scan"] = {"block": jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)}
    return out


def unstack_layer_params(backbone: Dict[str, Any], prefix: str = "h_") -> Dict[str, Any]:
    """Inverse of :func:`stack_layer_params` — for HF-format export and
    checkpoint interop with unscanned layouts."""
    if "h_scan" not in backbone:
        return backbone
    out = {k: v for k, v in backbone.items() if k != "h_scan"}
    stacked = backbone["h_scan"]["block"]
    num_layers = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    for i in range(num_layers):
        out[f"{prefix}{i}"] = jax.tree_util.tree_map(lambda p: p[i], stacked)
    return out


BUILTIN_SPECS = {
    "gpt2": TransformerConfig.gpt2,
    "llama": TransformerConfig.llama,
    "mistral": TransformerConfig.mistral,
    "mixtral": TransformerConfig.mixtral,
    "olmoe": TransformerConfig.olmoe,
    "smallthinker": TransformerConfig.smallthinker,
    "falconh1": TransformerConfig.falconh1,
    "pangu": TransformerConfig.pangu,
    "glm": TransformerConfig.glm,
    "k-exaone": TransformerConfig.exaone,
    "minicpm-sala": TransformerConfig.minicpm_sala,
    "kimi-linear": TransformerConfig.kimi_linear,
    "dots3": TransformerConfig.dots3,
    "lfm2": TransformerConfig.lfm2,
    "nemotron3": TransformerConfig.nemotron_h,
    "nemotron-h": TransformerConfig.nemotron_h,
    "gptj": TransformerConfig.gptj,
    "gptneox": TransformerConfig.gptneox,
    "pythia": TransformerConfig.gptneox,
    "opt": TransformerConfig.opt,
    "bloom": TransformerConfig.bloom,
}


def config_from_spec(spec: str, **overrides) -> TransformerConfig:
    """Parse a ``builtin:<family>-<size>`` model spec into a config."""
    if spec.startswith("builtin:"):
        spec = spec.split(":", 1)[1]
    # the longest family name the spec starts with (one has a hyphen of its own: k-exaone)
    family = max((f for f in BUILTIN_SPECS if spec == f or spec.startswith(f + "-")), key=len, default=spec.partition("-")[0])
    size = spec[len(family) + 1 :]
    if family not in BUILTIN_SPECS:
        raise ValueError(f"Unknown model family '{family}'. Known: {sorted(BUILTIN_SPECS)}")
    return BUILTIN_SPECS[family](size or "test", **overrides)


def write_row_spans(cache: jax.Array, x: jax.Array, ci: jax.Array) -> jax.Array:
    """``cache[b, ci[b] : ci[b] + T] = x[b]`` for every row ``b`` of a dense
    ``cache [B, S, KV, D]``, ``x [B, T, KV, D]`` and ``ci [B]``: ONE scatter of
    the index pairs ``(row, slot)`` with a window of ``[KV, D]``, as the ring
    branch of ``Attention`` writes. (A vmapped ``dynamic_update_slice`` is a
    scatter batched over the rows with a window of a whole row, which the chip
    runs one row at a time; a blend would read and write the whole cache.) The
    caller keeps ``0 <= ci`` and ``ci + T <= S``, as
    ``ops/speculative.py::spec_round_step`` does (its last probe lands on slot
    ``S - 1``): then nothing is dropped, and nothing was clipped. The slot
    engine's dense segment parks a row that ended by length at ``ci = S`` with
    ``T = 1``: that write, of a pad token's K and V, is dropped.

    (At the end of the file so that the lines above keep their numbers: a
    Pallas kernel's compile-cache key holds its callers' lines.)"""
    B, T = x.shape[:2]
    rows, at = jnp.arange(B)[:, None], ci[:, None] + jnp.arange(T)[None, :]
    return cache.at[rows, at].set(
        x.astype(cache.dtype), mode="drop", unique_indices=True, indices_are_sorted=True
    )
