"""Value / Q heads and LM wrapper modules.

Reference equivalents: ``make_head`` MLP (``trlx/utils/modeling.py:25-31``),
``AutoModelForCausalLMWithValueHead`` (``trlx/models/modeling_ppo.py:250-328``),
``ILQLHeads`` (``trlx/models/modeling_ilql.py:135-193``).

Target-Q heads are plain parameter subtrees: "frozen" means masked out of the
optimizer (``trlx_tpu/utils.get_optimizer(mask=...)``), and the Polyak sync is
a jitted ``tree_map`` over two subtrees — no module surgery needed.
"""

from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from trlx_tpu.models.transformer import (
    CausalTransformer,
    TransformerConfig,
    _dense,
    param_with_axes,
)


class MLPHead(nn.Module):
    """Two-layer MLP head: Linear(E→2E) → ReLU → Linear(2E→out)."""

    config: TransformerConfig
    out_features: int

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        h = _dense(cfg, 2 * cfg.hidden_size, True, ("embed", "mlp_head"), "in_proj")(x)
        h = nn.relu(h)
        # head outputs are tiny; compute in f32 for stable values/losses
        out = nn.Dense(
            self.out_features,
            use_bias=True,
            dtype=jnp.float32,
            param_dtype=cfg.param_dtype,
            kernel_init=param_with_axes(nn.initializers.normal(0.02), ("mlp_head", "head_out")),
            bias_init=param_with_axes(nn.initializers.zeros, ("head_out",)),
            name="out_proj",
        )(h)
        return out


class CausalLMWithValueHead(nn.Module):
    """Policy LM + scalar value head on the final hidden states."""

    config: TransformerConfig

    def setup(self):
        self.backbone = CausalTransformer(self.config, name="backbone")
        self.v_head = MLPHead(self.config, 1, name="v_head")

    def __call__(
        self,
        input_ids: jax.Array,
        attention_mask: Optional[jax.Array] = None,
        positions: Optional[jax.Array] = None,
        cache=None,
        cache_index=None,
        branch_layer: Optional[int] = None,
        logits_span: Optional[Tuple[int, int]] = None,
        kv_extents: Optional[Tuple[int, ...]] = None,
    ) -> Dict[str, Any]:
        out = self.backbone(
            input_ids,
            attention_mask=attention_mask,
            positions=positions,
            cache=cache,
            cache_index=cache_index,
            branch_layer=branch_layer,
            logits_span=logits_span,
            kv_extents=kv_extents,
        )
        out["value"] = self.v_head(out["hidden_states"])[..., 0]
        return out

    def forward_branch(
        self, hidden_states, branch_layer, attention_mask=None, positions=None, logits_span=None
    ):
        return self.backbone.forward_branch(
            hidden_states, branch_layer, attention_mask, positions, logits_span
        )

    def init_cache(self, batch_size, max_length, dtype=None):
        return self.backbone.init_cache(batch_size, max_length, dtype)

    def draft(self, hidden, next_ids, **kw):
        """The backbone's next-token-prediction module (``CausalTransformer.draft``)."""
        return self.backbone.draft(hidden, next_ids, **kw)


class ILQLHeadsModule(nn.Module):
    """V head + n Q heads + n frozen target-Q heads over hidden states."""

    config: TransformerConfig
    two_qs: bool = True

    def setup(self):
        n_qs = 2 if self.two_qs else 1
        self.v_head = MLPHead(self.config, 1, name="v_head")
        self.q_heads = [
            MLPHead(self.config, self.config.vocab_size, name=f"q_head_{i}") for i in range(n_qs)
        ]
        self.target_q_heads = [
            MLPHead(self.config, self.config.vocab_size, name=f"target_q_head_{i}")
            for i in range(n_qs)
        ]

    def __call__(self, hs: jax.Array) -> Tuple[Tuple[jax.Array, ...], Tuple[jax.Array, ...], jax.Array]:
        return self.heads_on(hs, hs)

    def heads_on(self, hs_actions: jax.Array, hs_states: jax.Array):
        """Q/target-Q heads on action positions, V head on state positions."""
        qs = tuple(q(hs_actions) for q in self.q_heads)
        target_qs = tuple(
            jax.lax.stop_gradient(q(hs_actions)) for q in self.target_q_heads
        )
        vs = self.v_head(hs_states)
        return qs, target_qs, vs


class CausalLMWithILQLHeads(nn.Module):
    """Policy LM + ILQL heads (V, twin Q, twin target-Q)."""

    config: TransformerConfig
    two_qs: bool = True

    def setup(self):
        self.backbone = CausalTransformer(self.config, name="backbone")
        self.ilql_heads = ILQLHeadsModule(self.config, self.two_qs, name="ilql_heads")

    def __call__(
        self,
        input_ids: jax.Array,
        attention_mask: Optional[jax.Array] = None,
        positions: Optional[jax.Array] = None,
        cache=None,
        cache_index=None,
        logits_span: Optional[Tuple[int, int]] = None,
        kv_extents: Optional[Tuple[int, ...]] = None,
    ) -> Dict[str, Any]:
        out = self.backbone(
            input_ids, attention_mask=attention_mask, positions=positions,
            cache=cache, cache_index=cache_index, logits_span=logits_span,
            kv_extents=kv_extents,
        )
        # the vocab-sized Q heads are as expensive as the lm head — restrict
        # them to the same span (V stays full: values are per-state scalars)
        hs = out["hidden_states"]
        hs_q = hs if logits_span is None else hs[:, logits_span[0] : logits_span[1]]
        qs, target_qs, vs = self.ilql_heads.heads_on(hs_q, hs)
        out.update(qs=qs, target_qs=target_qs, vs=vs)
        return out

    def init_cache(self, batch_size, max_length, dtype=None):
        return self.backbone.init_cache(batch_size, max_length, dtype)

    def backbone_forward(
        self, input_ids, attention_mask=None, positions=None, cache=None,
        cache_index=None, logits_span=None,
    ):
        """Backbone-only forward (no heads) — the training loss gathers
        hidden states at action/state indices first and applies heads to the
        gathered positions only (the reference's ``ILQLHeads.forward``
        index-select, ``trlx/models/modeling_ilql.py:160-180``)."""
        return self.backbone(
            input_ids,
            attention_mask=attention_mask,
            positions=positions,
            cache=cache,
            cache_index=cache_index,
            logits_span=logits_span,
        )

    def project_logits(self, hidden):
        """Vocab projection of gathered hidden states — the loss projects
        only the action positions instead of the full sequence, so the
        ``[B, T, V]`` logits tensor is never materialized."""
        return self.backbone.project_logits(hidden)

    def heads_on(self, hs_actions, hs_states):
        """Apply Q/target-Q heads at action positions, V head at states."""
        return self.ilql_heads.heads_on(hs_actions, hs_states)


def sync_target_q_params(params: Dict[str, Any], alpha: float) -> Dict[str, Any]:
    """Polyak update: target ← α·q + (1−α)·target.

    ``params`` is the full model param tree containing ``ilql_heads`` with
    ``q_head_i`` / ``target_q_head_i`` subtrees (reference semantics:
    ``modeling_ilql.py:182-193``).
    """
    heads = params["ilql_heads"]
    new_heads = dict(heads)
    for name in heads:
        if name.startswith("q_head_"):
            target_name = "target_" + name
            new_heads[target_name] = jax.tree_util.tree_map(
                lambda q, t: alpha * q + (1.0 - alpha) * t,
                heads[name],
                heads[target_name],
            )
    out = dict(params)
    out["ilql_heads"] = new_heads
    return out


# ---------------------------------------------------------------------------
# seq2seq (T5) wrappers — reference ``AutoModelForSeq2SeqLMWith(Hydra)ValueHead``
# (``trlx/models/modeling_ppo.py:948-1110``) and
# ``AutoModelForSeq2SeqLMWithILQLHeads`` (``modeling_ilql.py:347-488``).
# Heads attach to *decoder* hidden states.
# ---------------------------------------------------------------------------


class Seq2SeqLMWithValueHead(nn.Module):
    """T5 policy + scalar value head on decoder hidden states."""

    config: Any  # Seq2SeqConfig

    def setup(self):
        from trlx_tpu.models.seq2seq import T5Transformer

        self.backbone = T5Transformer(self.config, name="backbone")
        self.v_head = MLPHead(self.config, 1, name="v_head")

    def __call__(
        self,
        input_ids: jax.Array,
        attention_mask: Optional[jax.Array] = None,
        decoder_input_ids: Optional[jax.Array] = None,
        decoder_attention_mask: Optional[jax.Array] = None,
        branch_layer: Optional[int] = None,
    ) -> Dict[str, Any]:
        out = self.backbone(
            input_ids,
            attention_mask=attention_mask,
            decoder_input_ids=decoder_input_ids,
            decoder_attention_mask=decoder_attention_mask,
            branch_layer=branch_layer,
        )
        out["value"] = self.v_head(out["hidden_states"])[..., 0]
        return out

    def encode_for_decode(self, input_ids, attention_mask, max_decode_len):
        return self.backbone.encode_for_decode(input_ids, attention_mask, max_decode_len)

    def decode(self, decoder_input_ids, encoder_hidden, encoder_mask, cache=None, cache_index=None):
        out = self.backbone.decode(
            decoder_input_ids, encoder_hidden, encoder_mask, cache=cache, cache_index=cache_index
        )
        out["value"] = self.v_head(out["hidden_states"])[..., 0]
        return out

    def forward_branch(
        self, hidden_states, branch_layer, encoder_hidden, encoder_mask, decoder_mask=None
    ):
        return self.backbone.forward_branch(
            hidden_states, branch_layer, encoder_hidden, encoder_mask, decoder_mask
        )


class Seq2SeqLMWithILQLHeads(nn.Module):
    """T5 policy + ILQL heads (V, twin Q, twin target-Q) on decoder hiddens."""

    config: Any  # Seq2SeqConfig
    two_qs: bool = True

    def setup(self):
        from trlx_tpu.models.seq2seq import T5Transformer

        self.backbone = T5Transformer(self.config, name="backbone")
        self.ilql_heads = ILQLHeadsModule(self.config, self.two_qs, name="ilql_heads")

    def __call__(
        self,
        input_ids: jax.Array,
        attention_mask: Optional[jax.Array] = None,
        decoder_input_ids: Optional[jax.Array] = None,
        decoder_attention_mask: Optional[jax.Array] = None,
    ) -> Dict[str, Any]:
        out = self.backbone(
            input_ids,
            attention_mask=attention_mask,
            decoder_input_ids=decoder_input_ids,
            decoder_attention_mask=decoder_attention_mask,
        )
        qs, target_qs, vs = self.ilql_heads(out["hidden_states"])
        out.update(qs=qs, target_qs=target_qs, vs=vs)
        return out

    def backbone_forward(
        self,
        input_ids,
        attention_mask=None,
        decoder_input_ids=None,
        decoder_attention_mask=None,
        logits_span=None,
    ):
        return self.backbone(
            input_ids,
            attention_mask=attention_mask,
            decoder_input_ids=decoder_input_ids,
            decoder_attention_mask=decoder_attention_mask,
            logits_span=logits_span,
        )

    def project_logits(self, hidden):
        """Vocab projection of gathered decoder hidden states (the ILQL loss
        projects action positions only — see the causal twin)."""
        return self.backbone.project_logits(hidden)

    def heads_on(self, hs_actions, hs_states):
        return self.ilql_heads.heads_on(hs_actions, hs_states)

    def encode_for_decode(self, input_ids, attention_mask, max_decode_len):
        return self.backbone.encode_for_decode(input_ids, attention_mask, max_decode_len)

    def decode(self, decoder_input_ids, encoder_hidden, encoder_mask, cache=None, cache_index=None):
        out = self.backbone.decode(
            decoder_input_ids, encoder_hidden, encoder_mask, cache=cache, cache_index=cache_index
        )
        qs, target_qs, vs = self.ilql_heads(out["hidden_states"])
        out.update(qs=qs, target_qs=target_qs, vs=vs)
        return out
