"""HF (torch) checkpoint ⇄ trlx_tpu param-tree interop.

The reference wraps HF torch modules directly; here HF checkpoints are
*imported* into the native Flax parameter tree (and can be exported back via
``params_to_hf_state_dict``) — the interop equivalent of the reference's
sharded-checkpoint head merging (``trlx/models/modeling_base.py:142-184``,
``modeling_ppo.py:306-328``).

All converters are pure numpy: torch tensors → numpy → jax on first use.
Torch ``nn.Linear`` weights are [out, in] and transpose to Flax's [in, out];
GPT-2's Conv1D is already [in, out].
"""

from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from trlx_tpu.models.transformer import TransformerConfig


_FALCON_H1_NO_INTEROP = (
    "model_type 'falcon_h1' has no HF checkpoint conversion yet: the family runs "
    "from 'builtin:falconh1-<size>' (random weights) only; the converter pair was "
    "never checked against a checkpoint (ROADMAP.md queue 2, B7)"
)
_SMALLTHINKER_NO_INTEROP = (
    "model_type 'smallthinker' has no HF checkpoint conversion yet: no checkpoint can "
    "be fetched where this was built, so the family runs from "
    "'builtin:smallthinker-<size>' (random weights) only and no converter pair was "
    "ever checked against one (ROADMAP.md queue 2, B3)"
)
_PANGU_ULTRA_MOE_NO_INTEROP = (
    "model_type 'pangu_ultra_moe' has no HF checkpoint conversion yet: no checkpoint can "
    "be fetched where this was built, so the family runs from 'builtin:pangu-<size>' "
    "(random weights) only and no converter pair was ever checked against one "
    "(ROADMAP.md queue 2, B4)"
)
_GLM_MOE_DSA_NO_INTEROP = (
    "model_type 'glm_moe_dsa' has no HF checkpoint conversion yet: no checkpoint can "
    "be fetched where this was built, so the family runs from 'builtin:glm-<size>' "
    "(random weights) only and no converter pair was ever checked against one "
    "(ROADMAP.md queue 2, B8)"
)
_MINICPM_SALA_NO_INTEROP = (
    "model_type 'minicpm_sala' has no HF checkpoint conversion yet: no checkpoint can "
    "be fetched where this was built, so the family runs from 'builtin:minicpm-sala-<size>' "
    "(random weights) only and no converter pair was ever checked against one "
    "(ROADMAP.md queue 2, B7)"
)
_KIMI_LINEAR_NO_INTEROP = (
    "model_type 'kimi_linear' has no HF checkpoint conversion yet: no checkpoint can "
    "be fetched where this was built, so the family runs from 'builtin:kimi-linear-<size>' "
    "(random weights) only and no converter pair was ever checked against one "
    "(ROADMAP.md queue 2, B7)"
)
_DOTS3_NOTE_NO_INTEROP = (
    "model_type 'dots3_note' has no HF checkpoint conversion yet: no checkpoint can "
    "be fetched where this was built, so the family runs from 'builtin:dots3-note' "
    "(random weights) only and no converter pair was ever checked against one "
    "(ROADMAP.md queue 2, B4)"
)
_LFM2_MOE_NO_INTEROP = (
    "model_type 'lfm2_moe' has no HF checkpoint conversion yet: no checkpoint can "
    "be fetched where this was built, so the family runs from 'builtin:lfm2-<size>' "
    "(random weights) only and no converter pair was ever checked against one "
    "(ROADMAP.md queue 2, B7)"
)
_NEMOTRON_H_NO_INTEROP = (
    "model_type 'nemotron_h' has no HF checkpoint conversion yet: no checkpoint can "
    "be fetched where this was built, so the family runs from 'builtin:nemotron3-<size>' "
    "(random weights) only and no converter pair was ever checked against one "
    "(ROADMAP.md queue 2, B7)"
)
_EXAONE_MOE_NO_INTEROP = (
    "model_type 'exaone_moe' has no HF checkpoint conversion yet: no checkpoint can "
    "be fetched where this was built, so the family runs from 'builtin:k-exaone-<size>' "
    "(random weights) only and no converter pair was ever checked against one "
    "(ROADMAP.md queue 2, B6)"
)


class UnsupportedHFExport(ValueError):
    """Raised when an architecture has no transformers family mapping —
    the one 'skip HF export, keep the native msgpack' case. Genuine
    conversion bugs raise plain ValueError and must propagate."""


def _t(x: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(x.T)


def torch_state_dict_to_numpy(model) -> Dict[str, np.ndarray]:
    return {k: v.detach().cpu().numpy() for k, v in model.state_dict().items()}


def _ln(sd, prefix) -> Dict[str, np.ndarray]:
    out = {"scale": sd[f"{prefix}.weight"]}
    if f"{prefix}.bias" in sd:
        out["bias"] = sd[f"{prefix}.bias"]
    return out


def _split_headmajor_qkv(w: np.ndarray, b, num_heads: int, head_dim: int):
    """Split a fused qkv with head-major interleave ([H, 3, D, E] rows) into
    q/k/v [E, H*D] kernels (+ biases). Used by GPT-NeoX and BLOOM."""
    E = w.shape[1]
    w = w.reshape(num_heads, 3, head_dim, E)
    outs = []
    for j in range(3):
        kernel = _t(w[:, j].reshape(num_heads * head_dim, E))
        bias = None
        if b is not None:
            bias = b.reshape(num_heads, 3, head_dim)[:, j].reshape(-1)
        outs.append((kernel, bias))
    return outs


def _proj(kernel: np.ndarray, bias=None) -> Dict[str, np.ndarray]:
    out = {"kernel": kernel}
    if bias is not None:
        out["bias"] = bias
    return out


def convert_gpt2(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict[str, Any]:
    p = "transformer."
    E = cfg.hidden_size
    backbone: Dict[str, Any] = {
        "wte": {"embedding": sd[p + "wte.weight"]},
        "wpe": {"embedding": sd[p + "wpe.weight"]},
        "ln_f": _ln(sd, p + "ln_f"),
    }
    for i in range(cfg.num_layers):
        lp = f"{p}h.{i}."
        w = sd[lp + "attn.c_attn.weight"]  # Conv1D [E, 3E]
        b = sd[lp + "attn.c_attn.bias"]
        q_w, k_w, v_w = w[:, :E], w[:, E : 2 * E], w[:, 2 * E :]
        q_b, k_b, v_b = b[:E], b[E : 2 * E], b[2 * E :]
        backbone[f"h_{i}"] = {
            "ln_attn": _ln(sd, lp + "ln_1"),
            "ln_mlp": _ln(sd, lp + "ln_2"),
            "attn": {
                "q_proj": _proj(q_w, q_b),
                "k_proj": _proj(k_w, k_b),
                "v_proj": _proj(v_w, v_b),
                "o_proj": _proj(sd[lp + "attn.c_proj.weight"], sd[lp + "attn.c_proj.bias"]),
            },
            "mlp": {
                "up_proj": _proj(sd[lp + "mlp.c_fc.weight"], sd[lp + "mlp.c_fc.bias"]),
                "down_proj": _proj(sd[lp + "mlp.c_proj.weight"], sd[lp + "mlp.c_proj.bias"]),
            },
        }
    return {"backbone": backbone}


def convert_llama(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict[str, Any]:
    p = "model."
    backbone: Dict[str, Any] = {
        "wte": {"embedding": sd[p + "embed_tokens.weight"]},
        "ln_f": {"scale": sd[p + "norm.weight"]},
        "lm_head": {"kernel": _t(sd["lm_head.weight"])},
    }
    for i in range(cfg.num_layers):
        lp = f"{p}layers.{i}."
        backbone[f"h_{i}"] = {
            "ln_attn": {"scale": sd[lp + "input_layernorm.weight"]},
            "ln_mlp": {"scale": sd[lp + "post_attention_layernorm.weight"]},
            "attn": {
                "q_proj": _proj(_t(sd[lp + "self_attn.q_proj.weight"])),
                "k_proj": _proj(_t(sd[lp + "self_attn.k_proj.weight"])),
                "v_proj": _proj(_t(sd[lp + "self_attn.v_proj.weight"])),
                "o_proj": _proj(_t(sd[lp + "self_attn.o_proj.weight"])),
            },
            "mlp": {
                "gate_proj": _proj(_t(sd[lp + "mlp.gate_proj.weight"])),
                "up_proj": _proj(_t(sd[lp + "mlp.up_proj.weight"])),
                "down_proj": _proj(_t(sd[lp + "mlp.down_proj.weight"])),
            },
        }
    return {"backbone": backbone}


def convert_mixtral(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict[str, Any]:
    """Mixtral (llama-style attention + sparse MoE MLP): per-expert
    ``w1``/``w3``/``w2`` Linears stack into the ``[E, ...]`` expert kernels
    and the router ``gate`` Linear becomes the fp32 router Dense. A declared
    ``sliding_window`` maps onto the native windowed-attention masking."""
    p = "model."
    backbone: Dict[str, Any] = {
        "wte": {"embedding": sd[p + "embed_tokens.weight"]},
        "ln_f": {"scale": sd[p + "norm.weight"]},
        "lm_head": {"kernel": _t(sd["lm_head.weight"])},
    }
    E = cfg.num_experts
    for i in range(cfg.num_layers):
        lp = f"{p}layers.{i}."
        ep = lp + "block_sparse_moe."
        backbone[f"h_{i}"] = {
            "ln_attn": {"scale": sd[lp + "input_layernorm.weight"]},
            "ln_mlp": {"scale": sd[lp + "post_attention_layernorm.weight"]},
            "attn": {
                "q_proj": _proj(_t(sd[lp + "self_attn.q_proj.weight"])),
                "k_proj": _proj(_t(sd[lp + "self_attn.k_proj.weight"])),
                "v_proj": _proj(_t(sd[lp + "self_attn.v_proj.weight"])),
                "o_proj": _proj(_t(sd[lp + "self_attn.o_proj.weight"])),
            },
            "mlp": {
                "router": {"kernel": _t(sd[ep + "gate.weight"])},
                "w_gate": np.stack(
                    [_t(sd[f"{ep}experts.{e}.w1.weight"]) for e in range(E)]
                ),
                "w_up": np.stack(
                    [_t(sd[f"{ep}experts.{e}.w3.weight"]) for e in range(E)]
                ),
                "w_down": np.stack(
                    [_t(sd[f"{ep}experts.{e}.w2.weight"]) for e in range(E)]
                ),
            },
        }
    return {"backbone": backbone}


_OLMOE_EXPERT_KEYS = (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj"))


def convert_olmoe(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict[str, Any]:
    """OLMoE: the llama key layout plus ``self_attn.{q,k}_norm`` (RMSNorm
    over the whole projected width) and a sparse ``mlp`` whose router is
    ``mlp.gate`` and whose experts are ``mlp.experts.<e>.{gate,up,down}_proj``."""
    p = "model."
    backbone: Dict[str, Any] = {
        "wte": {"embedding": sd[p + "embed_tokens.weight"]},
        "ln_f": {"scale": sd[p + "norm.weight"]},
        "lm_head": {"kernel": _t(sd["lm_head.weight"])},
    }
    for i in range(cfg.num_layers):
        lp = f"{p}layers.{i}."
        ep = lp + "mlp.experts."
        attn = {
            name: _proj(_t(sd[f"{lp}self_attn.{name}.weight"]))
            for name in ("q_proj", "k_proj", "v_proj", "o_proj")
        }
        attn["q_norm"] = {"scale": sd[lp + "self_attn.q_norm.weight"]}
        attn["k_norm"] = {"scale": sd[lp + "self_attn.k_norm.weight"]}
        backbone[f"h_{i}"] = {
            "ln_attn": {"scale": sd[lp + "input_layernorm.weight"]},
            "ln_mlp": {"scale": sd[lp + "post_attention_layernorm.weight"]},
            "attn": attn,
            "mlp": {
                "router": {"kernel": _t(sd[lp + "mlp.gate.weight"])},
                **{
                    ours: np.stack(
                        [_t(sd[f"{ep}{e}.{theirs}.weight"]) for e in range(cfg.num_experts)]
                    )
                    for ours, theirs in _OLMOE_EXPERT_KEYS
                },
            },
        }
    return {"backbone": backbone}


def convert_gptneox(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict[str, Any]:
    p = "gpt_neox."
    D = cfg.dims_per_head
    backbone: Dict[str, Any] = {
        "wte": {"embedding": sd[p + "embed_in.weight"]},
        "ln_f": _ln(sd, p + "final_layer_norm"),
        "lm_head": {"kernel": _t(sd["embed_out.weight"])},
    }
    for i in range(cfg.num_layers):
        lp = f"{p}layers.{i}."
        (q_w, q_b), (k_w, k_b), (v_w, v_b) = _split_headmajor_qkv(
            sd[lp + "attention.query_key_value.weight"],
            sd.get(lp + "attention.query_key_value.bias"),
            cfg.num_heads,
            D,
        )
        backbone[f"h_{i}"] = {
            "ln_attn": _ln(sd, lp + "input_layernorm"),
            "ln_mlp": _ln(sd, lp + "post_attention_layernorm"),
            "attn": {
                "q_proj": _proj(q_w, q_b),
                "k_proj": _proj(k_w, k_b),
                "v_proj": _proj(v_w, v_b),
                "o_proj": _proj(_t(sd[lp + "attention.dense.weight"]), sd[lp + "attention.dense.bias"]),
            },
            "mlp": {
                "up_proj": _proj(_t(sd[lp + "mlp.dense_h_to_4h.weight"]), sd[lp + "mlp.dense_h_to_4h.bias"]),
                "down_proj": _proj(_t(sd[lp + "mlp.dense_4h_to_h.weight"]), sd[lp + "mlp.dense_4h_to_h.bias"]),
            },
        }
    return {"backbone": backbone}


def convert_gptj(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict[str, Any]:
    p = "transformer."
    backbone: Dict[str, Any] = {
        "wte": {"embedding": sd[p + "wte.weight"]},
        "ln_f": _ln(sd, p + "ln_f"),
        "lm_head": {"kernel": _t(sd["lm_head.weight"]), "bias": sd["lm_head.bias"]},
    }
    for i in range(cfg.num_layers):
        lp = f"{p}h.{i}."
        backbone[f"h_{i}"] = {
            "ln_attn": _ln(sd, lp + "ln_1"),
            "attn": {
                "q_proj": _proj(_t(sd[lp + "attn.q_proj.weight"])),
                "k_proj": _proj(_t(sd[lp + "attn.k_proj.weight"])),
                "v_proj": _proj(_t(sd[lp + "attn.v_proj.weight"])),
                "o_proj": _proj(_t(sd[lp + "attn.out_proj.weight"])),
            },
            "mlp": {
                "up_proj": _proj(_t(sd[lp + "mlp.fc_in.weight"]), sd[lp + "mlp.fc_in.bias"]),
                "down_proj": _proj(_t(sd[lp + "mlp.fc_out.weight"]), sd[lp + "mlp.fc_out.bias"]),
            },
        }
    return {"backbone": backbone}


def convert_opt(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict[str, Any]:
    p = "model.decoder."
    backbone: Dict[str, Any] = {
        "wte": {"embedding": sd[p + "embed_tokens.weight"]},
        "wpe": {"embedding": sd[p + "embed_positions.weight"]},
        "ln_f": _ln(sd, p + "final_layer_norm"),
    }
    for i in range(cfg.num_layers):
        lp = f"{p}layers.{i}."
        backbone[f"h_{i}"] = {
            "ln_attn": _ln(sd, lp + "self_attn_layer_norm"),
            "ln_mlp": _ln(sd, lp + "final_layer_norm"),
            "attn": {
                "q_proj": _proj(_t(sd[lp + "self_attn.q_proj.weight"]), sd[lp + "self_attn.q_proj.bias"]),
                "k_proj": _proj(_t(sd[lp + "self_attn.k_proj.weight"]), sd[lp + "self_attn.k_proj.bias"]),
                "v_proj": _proj(_t(sd[lp + "self_attn.v_proj.weight"]), sd[lp + "self_attn.v_proj.bias"]),
                "o_proj": _proj(_t(sd[lp + "self_attn.out_proj.weight"]), sd[lp + "self_attn.out_proj.bias"]),
            },
            "mlp": {
                "up_proj": _proj(_t(sd[lp + "fc1.weight"]), sd[lp + "fc1.bias"]),
                "down_proj": _proj(_t(sd[lp + "fc2.weight"]), sd[lp + "fc2.bias"]),
            },
        }
    return {"backbone": backbone}


def convert_bloom(sd: Dict[str, np.ndarray], cfg: TransformerConfig) -> Dict[str, Any]:
    p = "transformer."
    D = cfg.dims_per_head
    backbone: Dict[str, Any] = {
        "wte": {"embedding": sd[p + "word_embeddings.weight"]},
        "emb_ln": _ln(sd, p + "word_embeddings_layernorm"),
        "ln_f": _ln(sd, p + "ln_f"),
    }
    for i in range(cfg.num_layers):
        lp = f"{p}h.{i}."
        (q_w, q_b), (k_w, k_b), (v_w, v_b) = _split_headmajor_qkv(
            sd[lp + "self_attention.query_key_value.weight"],
            sd.get(lp + "self_attention.query_key_value.bias"),
            cfg.num_heads,
            D,
        )
        backbone[f"h_{i}"] = {
            "ln_attn": _ln(sd, lp + "input_layernorm"),
            "ln_mlp": _ln(sd, lp + "post_attention_layernorm"),
            "attn": {
                "q_proj": _proj(q_w, q_b),
                "k_proj": _proj(k_w, k_b),
                "v_proj": _proj(v_w, v_b),
                "o_proj": _proj(
                    _t(sd[lp + "self_attention.dense.weight"]), sd[lp + "self_attention.dense.bias"]
                ),
            },
            "mlp": {
                "up_proj": _proj(_t(sd[lp + "mlp.dense_h_to_4h.weight"]), sd[lp + "mlp.dense_h_to_4h.bias"]),
                "down_proj": _proj(_t(sd[lp + "mlp.dense_4h_to_h.weight"]), sd[lp + "mlp.dense_4h_to_h.bias"]),
            },
        }
    return {"backbone": backbone}


CONVERTERS: Dict[str, Callable] = {
    "gpt2": convert_gpt2,
    "llama": convert_llama,
    "gpt_neox": convert_gptneox,
    "gptj": convert_gptj,
    "opt": convert_opt,
    "bloom": convert_bloom,
    "mistral": convert_llama,  # identical key layout (llama + sliding window)
    "mixtral": convert_mixtral,
    "olmoe": convert_olmoe,
}


def config_from_hf(hf_config) -> TransformerConfig:
    """Map a transformers config object to a :class:`TransformerConfig`."""
    mt = hf_config.model_type
    if mt == "gpt2":
        return TransformerConfig(
            model_type=mt,
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.n_embd,
            num_layers=hf_config.n_layer,
            num_heads=hf_config.n_head,
            intermediate_size=hf_config.n_inner or 4 * hf_config.n_embd,
            max_position_embeddings=hf_config.n_positions,
            position_scheme="learned",
            activation="gelu_new",
            layer_norm_epsilon=hf_config.layer_norm_epsilon,
        )
    if mt in ("llama", "mistral"):
        # mistral IS the llama mapping + head_dim override + sliding window
        # (both getattrs are None-safe on LlamaConfig)
        return TransformerConfig(
            model_type=mt,
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            num_kv_heads=getattr(hf_config, "num_key_value_heads", None),
            head_dim=getattr(hf_config, "head_dim", None),
            intermediate_size=hf_config.intermediate_size,
            max_position_embeddings=hf_config.max_position_embeddings,
            position_scheme="rotary",
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            norm="rmsnorm",
            layer_norm_epsilon=hf_config.rms_norm_eps,
            activation="silu",
            attn_bias=False,
            mlp_bias=False,
            tie_word_embeddings=bool(getattr(hf_config, "tie_word_embeddings", False)),
            sliding_window=getattr(hf_config, "sliding_window", None),
        )
    if mt == "mixtral":
        return TransformerConfig(
            model_type=mt,
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            num_kv_heads=getattr(hf_config, "num_key_value_heads", None),
            intermediate_size=hf_config.intermediate_size,
            max_position_embeddings=hf_config.max_position_embeddings,
            position_scheme="rotary",
            rope_theta=getattr(hf_config, "rope_theta", 1e6),
            norm="rmsnorm",
            layer_norm_epsilon=hf_config.rms_norm_eps,
            activation="silu",
            attn_bias=False,
            mlp_bias=False,
            tie_word_embeddings=bool(getattr(hf_config, "tie_word_embeddings", False)),
            num_experts=hf_config.num_local_experts,
            moe_gated=True,
            num_experts_per_tok=hf_config.num_experts_per_tok,
            router_aux_coef=getattr(hf_config, "router_aux_loss_coef", 0.01),
            moe_group_size=512,
            sliding_window=getattr(hf_config, "sliding_window", None),
            # HF Mixtral routes with no capacity bound: dropless here too, so
            # imported checkpoints reproduce HF logits. Set a capacity factor
            # above 0 for expert-parallel dispatch (overflow tokens drop).
            moe_capacity_factor=0.0,
        )
    if mt == "olmoe":
        return TransformerConfig(
            model_type=mt,
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            num_kv_heads=getattr(hf_config, "num_key_value_heads", None),
            intermediate_size=hf_config.intermediate_size,
            max_position_embeddings=hf_config.max_position_embeddings,
            position_scheme="rotary",
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            norm="rmsnorm",
            layer_norm_epsilon=hf_config.rms_norm_eps,
            activation="silu",
            attn_bias=False,
            mlp_bias=False,
            tie_word_embeddings=bool(getattr(hf_config, "tie_word_embeddings", False)),
            qk_norm=True,
            num_experts=hf_config.num_experts,
            moe_gated=True,
            num_experts_per_tok=hf_config.num_experts_per_tok,
            moe_renormalize=bool(getattr(hf_config, "norm_topk_prob", False)),
            router_aux_coef=getattr(hf_config, "router_aux_loss_coef", 0.01),
            moe_capacity_factor=0.0,  # dropless, as published
        )
    if mt == "gpt_neox":
        head_dim = hf_config.hidden_size // hf_config.num_attention_heads
        return TransformerConfig(
            model_type=mt,
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            intermediate_size=hf_config.intermediate_size,
            max_position_embeddings=hf_config.max_position_embeddings,
            position_scheme="rotary",
            rotary_dim=int(head_dim * hf_config.rotary_pct),
            rope_theta=getattr(hf_config, "rotary_emb_base", 10000.0),
            activation="gelu",
            parallel_residual=bool(hf_config.use_parallel_residual),
            shared_ln=False,
            layer_norm_epsilon=hf_config.layer_norm_eps,
            tie_word_embeddings=False,
        )
    if mt == "gptj":
        return TransformerConfig(
            model_type=mt,
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.n_embd,
            num_layers=hf_config.n_layer,
            num_heads=hf_config.n_head,
            intermediate_size=hf_config.n_inner or 4 * hf_config.n_embd,
            max_position_embeddings=hf_config.n_positions,
            position_scheme="rotary",
            rotary_dim=hf_config.rotary_dim,
            activation="gelu_new",
            parallel_residual=True,
            shared_ln=True,
            attn_bias=False,
            qkv_bias=False,
            mlp_bias=True,
            layer_norm_epsilon=hf_config.layer_norm_epsilon,
            tie_word_embeddings=False,
            lm_head_bias=True,
        )
    if mt == "opt":
        return TransformerConfig(
            model_type=mt,
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            intermediate_size=hf_config.ffn_dim,
            max_position_embeddings=hf_config.max_position_embeddings,
            position_scheme="learned",
            pos_offset=2,
            activation=hf_config.activation_function,
            tie_word_embeddings=True,
        )
    if mt == "bloom":
        return TransformerConfig(
            model_type=mt,
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            num_layers=hf_config.n_layer,
            num_heads=hf_config.n_head,
            intermediate_size=4 * hf_config.hidden_size,
            max_position_embeddings=2048,
            position_scheme="alibi",
            activation="gelu",
            embedding_layernorm=True,
            layer_norm_epsilon=hf_config.layer_norm_epsilon,
            tie_word_embeddings=True,
        )
    if mt == "falcon_h1":
        raise ValueError(_FALCON_H1_NO_INTEROP)
    if mt == "smallthinker":
        raise ValueError(_SMALLTHINKER_NO_INTEROP)
    if mt == "pangu_ultra_moe":
        raise ValueError(_PANGU_ULTRA_MOE_NO_INTEROP)
    if mt == "glm_moe_dsa":
        raise ValueError(_GLM_MOE_DSA_NO_INTEROP)
    if mt == "exaone_moe":
        raise ValueError(_EXAONE_MOE_NO_INTEROP)
    if mt == "minicpm_sala":
        raise ValueError(_MINICPM_SALA_NO_INTEROP)
    if mt == "kimi_linear":
        raise ValueError(_KIMI_LINEAR_NO_INTEROP)
    if mt == "dots3_note":
        raise ValueError(_DOTS3_NOTE_NO_INTEROP)
    if mt == "lfm2_moe":
        raise ValueError(_LFM2_MOE_NO_INTEROP)
    if mt == "nemotron_h":
        raise ValueError(_NEMOTRON_H_NO_INTEROP)
    raise ValueError(f"Unsupported HF model type for causal import: {mt}")


def params_from_hf(model, cfg: TransformerConfig = None) -> Tuple[Dict[str, Any], TransformerConfig]:
    """Convert a loaded HF torch model into (params, config)."""
    if cfg is None:
        cfg = config_from_hf(model.config)
    sd = torch_state_dict_to_numpy(model)
    converter = CONVERTERS[model.config.model_type]
    return converter(sd, cfg), cfg


def load_pretrained(path: str) -> Tuple[Dict[str, Any], TransformerConfig]:
    """Load an HF checkpoint from a local path into (params, config)."""
    from transformers import AutoConfig, AutoModelForCausalLM

    hf_config = AutoConfig.from_pretrained(path)
    model = AutoModelForCausalLM.from_pretrained(path)
    return params_from_hf(model, config_from_hf(hf_config))


# ---------------------------------------------------------------------------
# seq2seq (T5 family) import — reference wraps HF T5 for its seq2seq path
# (``trlx/models/modeling_ppo.py:948-1222``); here the torch checkpoint is
# converted into the T5Transformer param tree.
# ---------------------------------------------------------------------------


def _t5_attn(sd, prefix) -> Dict[str, Any]:
    return {
        "q_proj": _proj(_t(sd[prefix + ".q.weight"])),
        "k_proj": _proj(_t(sd[prefix + ".k.weight"])),
        "v_proj": _proj(_t(sd[prefix + ".v.weight"])),
        "o_proj": _proj(_t(sd[prefix + ".o.weight"])),
    }


def _t5_mlp(sd, prefix, gated: bool) -> Dict[str, Any]:
    if gated:
        return {
            "gate_proj": _proj(_t(sd[prefix + ".wi_0.weight"])),
            "up_proj": _proj(_t(sd[prefix + ".wi_1.weight"])),
            "down_proj": _proj(_t(sd[prefix + ".wo.weight"])),
        }
    return {
        "up_proj": _proj(_t(sd[prefix + ".wi.weight"])),
        "down_proj": _proj(_t(sd[prefix + ".wo.weight"])),
    }


def convert_t5(sd: Dict[str, np.ndarray], cfg) -> Dict[str, Any]:
    """HF T5/Flan-T5 state dict → T5Transformer param tree."""
    gated = cfg.activation == "gated-gelu"
    backbone: Dict[str, Any] = {
        "wte": {"embedding": sd["shared.weight"]},
        "enc_rel_bias": {
            "rel_bias": {
                "embedding": sd[
                    "encoder.block.0.layer.0.SelfAttention.relative_attention_bias.weight"
                ]
            }
        },
        "dec_rel_bias": {
            "rel_bias": {
                "embedding": sd[
                    "decoder.block.0.layer.0.SelfAttention.relative_attention_bias.weight"
                ]
            }
        },
        "enc_ln_f": {"scale": sd["encoder.final_layer_norm.weight"]},
        "dec_ln_f": {"scale": sd["decoder.final_layer_norm.weight"]},
    }
    for i in range(cfg.num_layers):
        lp = f"encoder.block.{i}."
        backbone[f"enc_{i}"] = {
            "ln_self": {"scale": sd[lp + "layer.0.layer_norm.weight"]},
            "self_attn": _t5_attn(sd, lp + "layer.0.SelfAttention"),
            "ln_mlp": {"scale": sd[lp + "layer.1.layer_norm.weight"]},
            "mlp": _t5_mlp(sd, lp + "layer.1.DenseReluDense", gated),
        }
    for i in range(cfg.num_decoder_layers):
        lp = f"decoder.block.{i}."
        backbone[f"dec_{i}"] = {
            "ln_self": {"scale": sd[lp + "layer.0.layer_norm.weight"]},
            "self_attn": _t5_attn(sd, lp + "layer.0.SelfAttention"),
            "ln_cross": {"scale": sd[lp + "layer.1.layer_norm.weight"]},
            "cross_attn": _t5_attn(sd, lp + "layer.1.EncDecAttention"),
            "ln_mlp": {"scale": sd[lp + "layer.2.layer_norm.weight"]},
            "mlp": _t5_mlp(sd, lp + "layer.2.DenseReluDense", gated),
        }
    if not cfg.tie_word_embeddings:
        backbone["lm_head"] = _proj(_t(sd["lm_head.weight"]))
    return {"backbone": backbone}


def seq2seq_config_from_hf(hf_config):
    """Map a transformers T5Config to :class:`Seq2SeqConfig`."""
    from trlx_tpu.models.seq2seq import Seq2SeqConfig

    if hf_config.model_type not in ("t5", "mt5"):
        raise ValueError(f"Unsupported HF model type for seq2seq import: {hf_config.model_type}")
    act = hf_config.feed_forward_proj
    if act not in ("relu", "gated-gelu"):
        raise ValueError(
            f"Unsupported T5 feed_forward_proj '{act}' (supported: relu, gated-gelu)"
        )
    return Seq2SeqConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.d_model,
        num_layers=hf_config.num_layers,
        num_decoder_layers=hf_config.num_decoder_layers,
        num_heads=hf_config.num_heads,
        head_dim=hf_config.d_kv,
        intermediate_size=hf_config.d_ff,
        relative_attention_num_buckets=hf_config.relative_attention_num_buckets,
        relative_attention_max_distance=getattr(
            hf_config, "relative_attention_max_distance", 128
        ),
        layer_norm_epsilon=hf_config.layer_norm_epsilon,
        activation=act,
        tie_word_embeddings=bool(hf_config.tie_word_embeddings),
        decoder_start_token_id=hf_config.decoder_start_token_id or 0,
        pad_token_id=hf_config.pad_token_id or 0,
    )


def seq2seq_params_from_hf(model, cfg=None) -> Tuple[Dict[str, Any], Any]:
    if cfg is None:
        cfg = seq2seq_config_from_hf(model.config)
    sd = torch_state_dict_to_numpy(model)
    return convert_t5(sd, cfg), cfg


def load_pretrained_seq2seq(path: str):
    from transformers import AutoConfig, AutoModelForSeq2SeqLM

    hf_config = AutoConfig.from_pretrained(path)
    model = AutoModelForSeq2SeqLM.from_pretrained(path)
    return seq2seq_params_from_hf(model, seq2seq_config_from_hf(hf_config))


# ---------------------------------------------------------------------------
# Export: trlx_tpu param tree → HF (torch) checkpoint directory.
#
# Inverse of the import converters above, including the reference's head
# merging semantics: value/ILQL head weights are folded into the state dict
# under ``v_head.`` / ``ilql_heads.`` prefixes with the reference's own
# torch module names (``trlx/models/modeling_ppo.py:306-328``,
# ``modeling_ilql.py:322-344``), so a checkpoint exported here loads both in
# plain ``transformers`` (heads ignored) and in reference trlx (heads
# re-split).
# ---------------------------------------------------------------------------


def _fuse_headmajor_qkv(attn: Dict[str, Any], num_heads: int, head_dim: int):
    """Inverse of :func:`_split_headmajor_qkv`: q/k/v kernels [E, H*D] →
    fused [3*H*D, E] torch weight with head-major interleave (+ fused bias)."""
    E = attn["q_proj"]["kernel"].shape[0]
    ws = []
    for name in ("q_proj", "k_proj", "v_proj"):
        ws.append(_t(np.asarray(attn[name]["kernel"])).reshape(num_heads, head_dim, E))
    w = np.stack(ws, axis=1).reshape(num_heads * 3 * head_dim, E)
    b = None
    if "bias" in attn["q_proj"]:
        bs = [
            np.asarray(attn[name]["bias"]).reshape(num_heads, head_dim)
            for name in ("q_proj", "k_proj", "v_proj")
        ]
        b = np.stack(bs, axis=1).reshape(-1)
    return w, b


def _put_ln(sd: Dict[str, np.ndarray], prefix: str, ln: Dict[str, Any]) -> None:
    sd[f"{prefix}.weight"] = np.asarray(ln["scale"])
    if "bias" in ln:
        sd[f"{prefix}.bias"] = np.asarray(ln["bias"])


def _put_linear(sd, prefix, proj, transpose=True) -> None:
    kernel = np.asarray(proj["kernel"])
    sd[f"{prefix}.weight"] = _t(kernel) if transpose else kernel
    if "bias" in proj:
        sd[f"{prefix}.bias"] = np.asarray(proj["bias"])


def export_gpt2(backbone: Dict[str, Any], cfg) -> Dict[str, np.ndarray]:
    p = "transformer."
    sd: Dict[str, np.ndarray] = {
        p + "wte.weight": np.asarray(backbone["wte"]["embedding"]),
        p + "wpe.weight": np.asarray(backbone["wpe"]["embedding"]),
    }
    _put_ln(sd, p + "ln_f", backbone["ln_f"])
    for i in range(cfg.num_layers):
        lp = f"{p}h.{i}."
        h = backbone[f"h_{i}"]
        _put_ln(sd, lp + "ln_1", h["ln_attn"])
        _put_ln(sd, lp + "ln_2", h["ln_mlp"])
        attn = h["attn"]
        # Conv1D layout [in, out]: our kernels go in untransposed
        sd[lp + "attn.c_attn.weight"] = np.concatenate(
            [np.asarray(attn[k]["kernel"]) for k in ("q_proj", "k_proj", "v_proj")], axis=1
        )
        sd[lp + "attn.c_attn.bias"] = np.concatenate(
            [np.asarray(attn[k]["bias"]) for k in ("q_proj", "k_proj", "v_proj")]
        )
        _put_linear(sd, lp + "attn.c_proj", attn["o_proj"], transpose=False)
        _put_linear(sd, lp + "mlp.c_fc", h["mlp"]["up_proj"], transpose=False)
        _put_linear(sd, lp + "mlp.c_proj", h["mlp"]["down_proj"], transpose=False)
    sd["lm_head.weight"] = sd[p + "wte.weight"]  # tied
    return sd


def export_llama(backbone: Dict[str, Any], cfg) -> Dict[str, np.ndarray]:
    p = "model."
    sd: Dict[str, np.ndarray] = {
        p + "embed_tokens.weight": np.asarray(backbone["wte"]["embedding"]),
        p + "norm.weight": np.asarray(backbone["ln_f"]["scale"]),
    }
    if cfg.tie_word_embeddings:
        sd["lm_head.weight"] = sd[p + "embed_tokens.weight"]
    else:
        sd["lm_head.weight"] = _t(np.asarray(backbone["lm_head"]["kernel"]))
    for i in range(cfg.num_layers):
        lp = f"{p}layers.{i}."
        h = backbone[f"h_{i}"]
        sd[lp + "input_layernorm.weight"] = np.asarray(h["ln_attn"]["scale"])
        sd[lp + "post_attention_layernorm.weight"] = np.asarray(h["ln_mlp"]["scale"])
        for ours, theirs in (
            ("q_proj", "self_attn.q_proj"),
            ("k_proj", "self_attn.k_proj"),
            ("v_proj", "self_attn.v_proj"),
            ("o_proj", "self_attn.o_proj"),
        ):
            _put_linear(sd, lp + theirs, h["attn"][ours])
        for ours, theirs in (
            ("gate_proj", "mlp.gate_proj"),
            ("up_proj", "mlp.up_proj"),
            ("down_proj", "mlp.down_proj"),
        ):
            _put_linear(sd, lp + theirs, h["mlp"][ours])
    return sd


def export_gptneox(backbone: Dict[str, Any], cfg) -> Dict[str, np.ndarray]:
    p = "gpt_neox."
    sd: Dict[str, np.ndarray] = {
        p + "embed_in.weight": np.asarray(backbone["wte"]["embedding"]),
        "embed_out.weight": _t(np.asarray(backbone["lm_head"]["kernel"])),
    }
    _put_ln(sd, p + "final_layer_norm", backbone["ln_f"])
    for i in range(cfg.num_layers):
        lp = f"{p}layers.{i}."
        h = backbone[f"h_{i}"]
        _put_ln(sd, lp + "input_layernorm", h["ln_attn"])
        _put_ln(sd, lp + "post_attention_layernorm", h["ln_mlp"])
        w, b = _fuse_headmajor_qkv(h["attn"], cfg.num_heads, cfg.dims_per_head)
        sd[lp + "attention.query_key_value.weight"] = w
        if b is not None:
            sd[lp + "attention.query_key_value.bias"] = b
        _put_linear(sd, lp + "attention.dense", h["attn"]["o_proj"])
        _put_linear(sd, lp + "mlp.dense_h_to_4h", h["mlp"]["up_proj"])
        _put_linear(sd, lp + "mlp.dense_4h_to_h", h["mlp"]["down_proj"])
    return sd


def export_gptj(backbone: Dict[str, Any], cfg) -> Dict[str, np.ndarray]:
    p = "transformer."
    sd: Dict[str, np.ndarray] = {
        p + "wte.weight": np.asarray(backbone["wte"]["embedding"]),
        "lm_head.weight": _t(np.asarray(backbone["lm_head"]["kernel"])),
        "lm_head.bias": np.asarray(backbone["lm_head"]["bias"]),
    }
    _put_ln(sd, p + "ln_f", backbone["ln_f"])
    for i in range(cfg.num_layers):
        lp = f"{p}h.{i}."
        h = backbone[f"h_{i}"]
        _put_ln(sd, lp + "ln_1", h["ln_attn"])
        for ours, theirs in (
            ("q_proj", "attn.q_proj"),
            ("k_proj", "attn.k_proj"),
            ("v_proj", "attn.v_proj"),
            ("o_proj", "attn.out_proj"),
        ):
            _put_linear(sd, lp + theirs, h["attn"][ours])
        _put_linear(sd, lp + "mlp.fc_in", h["mlp"]["up_proj"])
        _put_linear(sd, lp + "mlp.fc_out", h["mlp"]["down_proj"])
    return sd


def export_opt(backbone: Dict[str, Any], cfg) -> Dict[str, np.ndarray]:
    p = "model.decoder."
    sd: Dict[str, np.ndarray] = {
        p + "embed_tokens.weight": np.asarray(backbone["wte"]["embedding"]),
        p + "embed_positions.weight": np.asarray(backbone["wpe"]["embedding"]),
        "lm_head.weight": np.asarray(backbone["wte"]["embedding"]),  # tied
    }
    _put_ln(sd, p + "final_layer_norm", backbone["ln_f"])
    for i in range(cfg.num_layers):
        lp = f"{p}layers.{i}."
        h = backbone[f"h_{i}"]
        _put_ln(sd, lp + "self_attn_layer_norm", h["ln_attn"])
        _put_ln(sd, lp + "final_layer_norm", h["ln_mlp"])
        for ours, theirs in (
            ("q_proj", "self_attn.q_proj"),
            ("k_proj", "self_attn.k_proj"),
            ("v_proj", "self_attn.v_proj"),
            ("o_proj", "self_attn.out_proj"),
        ):
            _put_linear(sd, lp + theirs, h["attn"][ours])
        _put_linear(sd, lp + "fc1", h["mlp"]["up_proj"])
        _put_linear(sd, lp + "fc2", h["mlp"]["down_proj"])
    return sd


def export_bloom(backbone: Dict[str, Any], cfg) -> Dict[str, np.ndarray]:
    p = "transformer."
    sd: Dict[str, np.ndarray] = {
        p + "word_embeddings.weight": np.asarray(backbone["wte"]["embedding"]),
        "lm_head.weight": np.asarray(backbone["wte"]["embedding"]),  # tied
    }
    _put_ln(sd, p + "word_embeddings_layernorm", backbone["emb_ln"])
    _put_ln(sd, p + "ln_f", backbone["ln_f"])
    for i in range(cfg.num_layers):
        lp = f"{p}h.{i}."
        h = backbone[f"h_{i}"]
        _put_ln(sd, lp + "input_layernorm", h["ln_attn"])
        _put_ln(sd, lp + "post_attention_layernorm", h["ln_mlp"])
        w, b = _fuse_headmajor_qkv(h["attn"], cfg.num_heads, cfg.dims_per_head)
        sd[lp + "self_attention.query_key_value.weight"] = w
        if b is not None:
            sd[lp + "self_attention.query_key_value.bias"] = b
        _put_linear(sd, lp + "self_attention.dense", h["attn"]["o_proj"])
        _put_linear(sd, lp + "mlp.dense_h_to_4h", h["mlp"]["up_proj"])
        _put_linear(sd, lp + "mlp.dense_4h_to_h", h["mlp"]["down_proj"])
    return sd


def export_mixtral(backbone: Dict[str, Any], cfg) -> Dict[str, np.ndarray]:
    """Inverse of :func:`convert_mixtral`: expert kernels unstack into the
    per-expert ``w1``/``w3``/``w2`` Linears of MixtralForCausalLM."""
    p = "model."
    sd: Dict[str, np.ndarray] = {
        p + "embed_tokens.weight": np.asarray(backbone["wte"]["embedding"]),
        p + "norm.weight": np.asarray(backbone["ln_f"]["scale"]),
        "lm_head.weight": (
            np.asarray(backbone["wte"]["embedding"])
            if cfg.tie_word_embeddings
            else _t(np.asarray(backbone["lm_head"]["kernel"]))
        ),
    }
    for i in range(cfg.num_layers):
        lp = f"{p}layers.{i}."
        ep = lp + "block_sparse_moe."
        h = backbone[f"h_{i}"]
        sd[lp + "input_layernorm.weight"] = np.asarray(h["ln_attn"]["scale"])
        sd[lp + "post_attention_layernorm.weight"] = np.asarray(h["ln_mlp"]["scale"])
        for ours, theirs in (
            ("q_proj", "self_attn.q_proj"),
            ("k_proj", "self_attn.k_proj"),
            ("v_proj", "self_attn.v_proj"),
            ("o_proj", "self_attn.o_proj"),
        ):
            _put_linear(sd, lp + theirs, h["attn"][ours])
        mlp = h["mlp"]
        sd[ep + "gate.weight"] = _t(np.asarray(mlp["router"]["kernel"]))
        for e in range(cfg.num_experts):
            sd[f"{ep}experts.{e}.w1.weight"] = _t(np.asarray(mlp["w_gate"][e]))
            sd[f"{ep}experts.{e}.w3.weight"] = _t(np.asarray(mlp["w_up"][e]))
            sd[f"{ep}experts.{e}.w2.weight"] = _t(np.asarray(mlp["w_down"][e]))
    return sd


def export_t5(backbone: Dict[str, Any], cfg) -> Dict[str, np.ndarray]:
    """Inverse of :func:`convert_t5`: T5Transformer param tree → HF
    T5ForConditionalGeneration state dict (the seq2seq leg of the
    reference's save path, ``trlx/models/modeling_ppo.py:1036-1113`` +
    ``accelerate_base_trainer.py:256-272``)."""
    gated = cfg.activation == "gated-gelu"
    shared = np.asarray(backbone["wte"]["embedding"])
    sd: Dict[str, np.ndarray] = {
        "shared.weight": shared,
        # tied aliases transformers includes in its own state dicts
        "encoder.embed_tokens.weight": shared,
        "decoder.embed_tokens.weight": shared,
        "encoder.block.0.layer.0.SelfAttention.relative_attention_bias.weight": np.asarray(
            backbone["enc_rel_bias"]["rel_bias"]["embedding"]
        ),
        "decoder.block.0.layer.0.SelfAttention.relative_attention_bias.weight": np.asarray(
            backbone["dec_rel_bias"]["rel_bias"]["embedding"]
        ),
        "encoder.final_layer_norm.weight": np.asarray(backbone["enc_ln_f"]["scale"]),
        "decoder.final_layer_norm.weight": np.asarray(backbone["dec_ln_f"]["scale"]),
    }

    def put_attn(prefix: str, attn: Dict[str, Any]) -> None:
        for ours, theirs in (
            ("q_proj", "q"), ("k_proj", "k"), ("v_proj", "v"), ("o_proj", "o"),
        ):
            sd[f"{prefix}.{theirs}.weight"] = _t(np.asarray(attn[ours]["kernel"]))

    def put_mlp(prefix: str, mlp: Dict[str, Any]) -> None:
        if gated:
            sd[f"{prefix}.wi_0.weight"] = _t(np.asarray(mlp["gate_proj"]["kernel"]))
            sd[f"{prefix}.wi_1.weight"] = _t(np.asarray(mlp["up_proj"]["kernel"]))
        else:
            sd[f"{prefix}.wi.weight"] = _t(np.asarray(mlp["up_proj"]["kernel"]))
        sd[f"{prefix}.wo.weight"] = _t(np.asarray(mlp["down_proj"]["kernel"]))

    for i in range(cfg.num_layers):
        lp = f"encoder.block.{i}."
        h = backbone[f"enc_{i}"]
        sd[lp + "layer.0.layer_norm.weight"] = np.asarray(h["ln_self"]["scale"])
        put_attn(lp + "layer.0.SelfAttention", h["self_attn"])
        sd[lp + "layer.1.layer_norm.weight"] = np.asarray(h["ln_mlp"]["scale"])
        put_mlp(lp + "layer.1.DenseReluDense", h["mlp"])
    for i in range(cfg.num_decoder_layers):
        lp = f"decoder.block.{i}."
        h = backbone[f"dec_{i}"]
        sd[lp + "layer.0.layer_norm.weight"] = np.asarray(h["ln_self"]["scale"])
        put_attn(lp + "layer.0.SelfAttention", h["self_attn"])
        sd[lp + "layer.1.layer_norm.weight"] = np.asarray(h["ln_cross"]["scale"])
        put_attn(lp + "layer.1.EncDecAttention", h["cross_attn"])
        sd[lp + "layer.2.layer_norm.weight"] = np.asarray(h["ln_mlp"]["scale"])
        put_mlp(lp + "layer.2.DenseReluDense", h["mlp"])
    sd["lm_head.weight"] = (
        shared if cfg.tie_word_embeddings
        else _t(np.asarray(backbone["lm_head"]["kernel"]))
    )
    return sd


def export_olmoe(backbone: Dict[str, Any], cfg) -> Dict[str, np.ndarray]:
    """Inverse of :func:`convert_olmoe`."""
    p = "model."
    sd: Dict[str, np.ndarray] = {
        p + "embed_tokens.weight": np.asarray(backbone["wte"]["embedding"]),
        p + "norm.weight": np.asarray(backbone["ln_f"]["scale"]),
        "lm_head.weight": (
            np.asarray(backbone["wte"]["embedding"])
            if cfg.tie_word_embeddings
            else _t(np.asarray(backbone["lm_head"]["kernel"]))
        ),
    }
    for i in range(cfg.num_layers):
        lp = f"{p}layers.{i}."
        h = backbone[f"h_{i}"]
        sd[lp + "input_layernorm.weight"] = np.asarray(h["ln_attn"]["scale"])
        sd[lp + "post_attention_layernorm.weight"] = np.asarray(h["ln_mlp"]["scale"])
        for name in ("q_proj", "k_proj", "v_proj", "o_proj"):
            _put_linear(sd, f"{lp}self_attn.{name}", h["attn"][name])
        sd[lp + "self_attn.q_norm.weight"] = np.asarray(h["attn"]["q_norm"]["scale"])
        sd[lp + "self_attn.k_norm.weight"] = np.asarray(h["attn"]["k_norm"]["scale"])
        mlp = h["mlp"]
        sd[lp + "mlp.gate.weight"] = _t(np.asarray(mlp["router"]["kernel"]))
        for e in range(cfg.num_experts):
            for ours, theirs in _OLMOE_EXPERT_KEYS:
                sd[f"{lp}mlp.experts.{e}.{theirs}.weight"] = _t(np.asarray(mlp[ours][e]))
    return sd


EXPORTERS: Dict[str, Callable] = {
    "gpt2": export_gpt2,
    "llama": export_llama,
    "gpt_neox": export_gptneox,
    "gptj": export_gptj,
    "opt": export_opt,
    "bloom": export_bloom,
    "t5": export_t5,
    "mistral": export_llama,  # identical key layout
    "mixtral": export_mixtral,
    "olmoe": export_olmoe,
}


def _export_mlp_head(sd: Dict[str, np.ndarray], prefix: str, head: Dict[str, Any]) -> None:
    """MLPHead → reference ``make_head`` Sequential(Linear, ReLU, Linear)
    torch names: ``{prefix}.0.*`` / ``{prefix}.2.*``."""
    _put_linear(sd, f"{prefix}.0", head["in_proj"])
    _put_linear(sd, f"{prefix}.2", head["out_proj"])


def merge_heads_into_state_dict(sd: Dict[str, np.ndarray], params: Dict[str, Any]) -> None:
    """Fold value/ILQL head params into ``sd`` under the reference's key
    names (``modeling_ppo.py:306-328``, ``modeling_ilql.py:322-344``)."""
    if "v_head" in params:
        _export_mlp_head(sd, "v_head", params["v_head"])
    if "ilql_heads" in params:
        heads = params["ilql_heads"]
        _export_mlp_head(sd, "ilql_heads.heads.v_head", heads["v_head"])
        for name, tree in sorted(heads.items()):
            if name.startswith("q_head_"):
                i = int(name[len("q_head_") :])
                _export_mlp_head(sd, f"ilql_heads.heads.q_heads.{i}", tree)
            elif name.startswith("target_q_head_"):
                i = int(name[len("target_q_head_") :])
                _export_mlp_head(sd, f"ilql_heads.heads.target_q_heads.{i}", tree)


def hf_config_from_transformer(cfg):
    """Inverse of :func:`config_from_hf`: TransformerConfig → transformers
    config object for the family in ``cfg.model_type``."""
    import transformers as tf

    mt = cfg.model_type
    if mt == "t5":
        return tf.T5Config(
            vocab_size=cfg.vocab_size,
            d_model=cfg.hidden_size,
            d_kv=cfg.head_dim,
            d_ff=cfg.intermediate_size,
            num_layers=cfg.num_layers,
            num_decoder_layers=cfg.num_decoder_layers,
            num_heads=cfg.num_heads,
            relative_attention_num_buckets=cfg.relative_attention_num_buckets,
            relative_attention_max_distance=cfg.relative_attention_max_distance,
            layer_norm_epsilon=cfg.layer_norm_epsilon,
            feed_forward_proj=cfg.activation,
            tie_word_embeddings=cfg.tie_word_embeddings,
            decoder_start_token_id=cfg.decoder_start_token_id,
            pad_token_id=cfg.pad_token_id,
        )
    if mt == "gpt2":
        return tf.GPT2Config(
            vocab_size=cfg.vocab_size,
            n_positions=cfg.max_position_embeddings,
            n_embd=cfg.hidden_size,
            n_layer=cfg.num_layers,
            n_head=cfg.num_heads,
            n_inner=cfg.intermediate_size,
            layer_norm_epsilon=cfg.layer_norm_epsilon,
        )
    if mt in ("llama", "mistral"):
        shared = dict(
            vocab_size=cfg.vocab_size,
            hidden_size=cfg.hidden_size,
            num_hidden_layers=cfg.num_layers,
            num_attention_heads=cfg.num_heads,
            num_key_value_heads=cfg.kv_heads,
            intermediate_size=cfg.intermediate_size,
            max_position_embeddings=cfg.max_position_embeddings,
            rms_norm_eps=cfg.layer_norm_epsilon,
            rope_theta=cfg.rope_theta,
            tie_word_embeddings=cfg.tie_word_embeddings,
        )
        if mt == "llama":
            return tf.LlamaConfig(**shared)
        return tf.MistralConfig(
            head_dim=cfg.dims_per_head,
            sliding_window=cfg.sliding_window,
            **shared,
        )
    if mt == "mixtral":
        return tf.MixtralConfig(
            vocab_size=cfg.vocab_size,
            hidden_size=cfg.hidden_size,
            num_hidden_layers=cfg.num_layers,
            num_attention_heads=cfg.num_heads,
            num_key_value_heads=cfg.kv_heads,
            intermediate_size=cfg.intermediate_size,
            max_position_embeddings=cfg.max_position_embeddings,
            rms_norm_eps=cfg.layer_norm_epsilon,
            rope_theta=cfg.rope_theta,
            num_local_experts=cfg.num_experts,
            num_experts_per_tok=cfg.num_experts_per_tok,
            router_aux_loss_coef=cfg.router_aux_coef,
            sliding_window=cfg.sliding_window,
            tie_word_embeddings=cfg.tie_word_embeddings,
        )
    if mt == "olmoe":
        return tf.OlmoeConfig(
            vocab_size=cfg.vocab_size,
            hidden_size=cfg.hidden_size,
            num_hidden_layers=cfg.num_layers,
            num_attention_heads=cfg.num_heads,
            num_key_value_heads=cfg.kv_heads,
            intermediate_size=cfg.intermediate_size,
            max_position_embeddings=cfg.max_position_embeddings,
            rms_norm_eps=cfg.layer_norm_epsilon,
            rope_theta=cfg.rope_theta,
            num_experts=cfg.num_experts,
            num_experts_per_tok=cfg.num_experts_per_tok,
            norm_topk_prob=cfg.moe_renormalize,
            router_aux_loss_coef=cfg.router_aux_coef,
            tie_word_embeddings=cfg.tie_word_embeddings,
        )
    if mt == "gpt_neox":
        return tf.GPTNeoXConfig(
            vocab_size=cfg.vocab_size,
            hidden_size=cfg.hidden_size,
            num_hidden_layers=cfg.num_layers,
            num_attention_heads=cfg.num_heads,
            intermediate_size=cfg.intermediate_size,
            max_position_embeddings=cfg.max_position_embeddings,
            rotary_pct=(cfg.rotary_dim or cfg.dims_per_head) / cfg.dims_per_head,
            rotary_emb_base=cfg.rope_theta,
            use_parallel_residual=cfg.parallel_residual,
            layer_norm_eps=cfg.layer_norm_epsilon,
            tie_word_embeddings=False,
        )
    if mt == "gptj":
        return tf.GPTJConfig(
            vocab_size=cfg.vocab_size,
            n_positions=cfg.max_position_embeddings,
            n_embd=cfg.hidden_size,
            n_layer=cfg.num_layers,
            n_head=cfg.num_heads,
            n_inner=cfg.intermediate_size,
            rotary_dim=cfg.rotary_dim,
            layer_norm_epsilon=cfg.layer_norm_epsilon,
            tie_word_embeddings=False,
        )
    if mt == "opt":
        return tf.OPTConfig(
            vocab_size=cfg.vocab_size,
            hidden_size=cfg.hidden_size,
            num_hidden_layers=cfg.num_layers,
            num_attention_heads=cfg.num_heads,
            ffn_dim=cfg.intermediate_size,
            max_position_embeddings=cfg.max_position_embeddings,
            activation_function=cfg.activation,
            word_embed_proj_dim=cfg.hidden_size,
            do_layer_norm_before=True,
        )
    if mt == "bloom":
        return tf.BloomConfig(
            vocab_size=cfg.vocab_size,
            hidden_size=cfg.hidden_size,
            n_layer=cfg.num_layers,
            n_head=cfg.num_heads,
            layer_norm_epsilon=cfg.layer_norm_epsilon,
        )
    if mt == "falcon_h1":
        raise UnsupportedHFExport(_FALCON_H1_NO_INTEROP)
    if mt == "smallthinker":
        raise UnsupportedHFExport(_SMALLTHINKER_NO_INTEROP)
    if mt == "pangu_ultra_moe":
        raise UnsupportedHFExport(_PANGU_ULTRA_MOE_NO_INTEROP)
    if mt == "glm_moe_dsa":
        raise UnsupportedHFExport(_GLM_MOE_DSA_NO_INTEROP)
    if mt == "exaone_moe":
        raise UnsupportedHFExport(_EXAONE_MOE_NO_INTEROP)
    if mt == "minicpm_sala":
        raise UnsupportedHFExport(_MINICPM_SALA_NO_INTEROP)
    if mt == "kimi_linear":
        raise UnsupportedHFExport(_KIMI_LINEAR_NO_INTEROP)
    if mt == "dots3_note":
        raise UnsupportedHFExport(_DOTS3_NOTE_NO_INTEROP)
    if mt == "lfm2_moe":
        raise UnsupportedHFExport(_LFM2_MOE_NO_INTEROP)
    if mt == "nemotron_h":
        raise UnsupportedHFExport(_NEMOTRON_H_NO_INTEROP)
    raise UnsupportedHFExport(
        f"No HF export mapping for model_type={mt!r} "
        "(set TransformerConfig.model_type to an HF family)"
    )


def params_to_hf_state_dict(params: Dict[str, Any], cfg) -> Dict[str, np.ndarray]:
    """Full param tree (backbone + any heads) → HF torch-layout state dict.

    Handles the scan_layers stacked layout and folds trained LoRA adapters
    into their base kernels (reference exports merged weights too — OpenDelta
    merges on save).
    """
    from trlx_tpu.models.builder import merge_lora_params
    from trlx_tpu.models.transformer import unstack_layer_params

    if cfg.model_type not in EXPORTERS:
        raise UnsupportedHFExport(
            f"No HF exporter for model_type={cfg.model_type!r}; known: {sorted(EXPORTERS)}"
        )
    backbone = params.get("backbone", params)
    backbone = unstack_layer_params(backbone)
    backbone = merge_lora_params(backbone, cfg)
    sd = EXPORTERS[cfg.model_type](backbone, cfg)
    if "backbone" in params:
        merge_heads_into_state_dict(sd, params)
    return sd


def save_pretrained_hf(
    directory: str,
    params: Dict[str, Any],
    cfg,
    tokenizer_path: Optional[str] = None,
) -> None:
    """Write a transformers-loadable checkpoint directory:
    ``pytorch_model.bin`` (fp32 torch tensors, heads merged under their
    reference prefixes) + ``config.json``; tokenizer files are copied when
    ``tokenizer_path`` is a local directory. The reference's
    ``save_pretrained`` contract (``accelerate_base_trainer.py:256-272``)."""
    import os
    import shutil

    import torch

    os.makedirs(directory, exist_ok=True)
    sd = params_to_hf_state_dict(params, cfg)
    tensors = {
        k: torch.tensor(np.asarray(v, dtype=np.float32)) for k, v in sd.items()
    }
    torch.save(tensors, os.path.join(directory, "pytorch_model.bin"))
    hf_config_from_transformer(cfg).save_pretrained(directory)
    if tokenizer_path and os.path.isdir(tokenizer_path):
        for name in os.listdir(tokenizer_path):
            if "token" in name or name in ("vocab.json", "merges.txt", "special_tokens_map.json"):
                shutil.copy(os.path.join(tokenizer_path, name), directory)
