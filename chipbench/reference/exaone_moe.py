"""K-EXAONE-236B-A23B's forward pass, plainly.

Written from the published ``LGAI-EXAONE/K-EXAONE-236B-A23B`` ``config.json``
(``model_type`` ``exaone_moe``): a pre-norm decoder of 48 layers, RMSNorm (eps
1e-5, learned scale), no bias anywhere, an untied head, and ONE
next-token-prediction module behind the stack. ``x`` is the residual stream.

- Block ``l``: ``a = x + Attn_l(N1(x))``, ``y = a + FFN_l(N2(a))``: two
  RMSNorms a layer.
- Attention: ``q = Wq h``, ``k = Wk h``, ``v = Wv h``; 64 query heads and 8
  key/value heads of 128 (query heads ``8j .. 8j+7`` share key/value head
  ``j``); each head's q and each key/value head's k through an RMSNorm over
  its 128 dims with a learned scale (``q_norm``, ``k_norm``: one scale of 128
  that all heads share); scale ``1/sqrt(128)``, causal. The layer's kind comes
  from the published ``layer_types[l]`` / ``sliding_windows[l]``: a
  ``sliding_attention`` layer sees its last 128 positions, itself included,
  and gets rotary embeddings over the whole head (split-half pairing, theta
  1,000,000); a ``full_attention`` layer attends over everything before it
  with NO positional encoding at all.
- Layer 0 (``mlp_layer_types[0]`` ``dense``): SwiGLU ``down(silu(gate n) *
  up n)`` at ``intermediate_size``.
- The other layers: ``s = sigmoid(n Wr)`` over the router's 128; the 8
  largest; ``g = 2.5 * s_top / sum(s_top)`` (``norm_topk_prob``,
  ``routed_scaling_factor``; ``n_group`` 1, ``topk_group`` 1: no group limit);
  ``y = sum_e g_e E_e(n) + S(n)``, ``E_e`` and the shared expert ``S`` SwiGLU
  of ``moe_intermediate_size``; no capacity bound.
- Final RMSNorm, untied head: ``logits``.
- The next-token-prediction module (``num_nextn_predict_layers`` 1): ``h'_t =
  Wm [N_h(h_t) ; N_e(Emb(x_{t+1}))]``, ``h_t`` the last layer's output BEFORE
  the final norm; one block of the kind above with FULL attention
  (``mtp_layer_types``) and no rotary over its own keys and values, its FFN
  sparse as layer 47's; a final RMSNorm of its own; logits through the main
  head: ``mtp_logits``, a distribution over ``x_{t+2}`` at position ``t``.

Plain ``jax.numpy`` in float32 under ``highest`` matmul precision: no kernel,
no cache, no ring, no sort, no grouped matmul, no draft-and-verify. The gate is
a dense ``[tokens, router width]`` matrix, every held expert is applied to
every token, one expert at a time; attention a block of ``Q_BLOCK`` query rows
at a time against all keys. It walks the system's own parameter tree one layer
at a time and casts that layer up. The sizes come from ``dims``, the published
keys of the configuration file. A projection that carries a LoRA adapter
(``lora_a``, ``lora_b`` beside its ``kernel``: the cell trains adapters) adds
``(alpha / r) x A B``, ``alpha`` from ``dims["lora_alpha"]``.

**One chip's share.** The expert kernels of the tree hold
``dims["num_experts"]`` experts, the slice ``[first, first + held)`` of the
router's width (``first`` is ``dims["moe_first_expert_held"]``, 0 if absent;
the width is the router kernel's). The reference is given the same share as
the program: it routes over the whole width, renormalises over all eight
chosen, and adds only what the held experts give, and the shared expert whole
(every chip of the deployment computes it alike). ``moe_layer`` is that one
layer alone, its routed part and its shared part apart, for the test that the
shares add up to the uncut layer with the shared expert counted once.

Departures from the publication, all of them: left padding gets positions
``cumsum(mask) - 1``; the window is on slot distance, which is position
distance because padding is left-only. Readings the config does not settle
are taken as the configuration file's ``assumed`` says, each with its other
reading as a planted fault below.

``fault`` plants a known error for the yardstick's control run:
``"no_window"`` lets the sliding layers see everything before them,
``"rope_on_global"`` gives the full layers rotary embeddings,
``"no_rope_on_window"`` takes them from the sliding layers, ``"no_qk_norm"``
drops the per-head norms, ``"qk_norm_whole_width"`` norms q and k over all
heads together (OLMoE's reading; the scale of 128 repeated a head),
``"post_norm"`` is EXAONE 4.0's block, a norm on each sublayer's OUTPUT (``x +
N1(Attn(x))``, ``a + N2(FFN(a))``), ``"softmax_router"`` scores with a softmax
over the experts, ``"no_routed_scaling"`` drops the 2.5, ``"no_topk_renorm"``
uses the eight scores as they are, ``"no_shared_expert"`` drops the shared
expert, ``"first_layer_sparse"`` reads ``first_k_dense_replace`` as 0 (layer 0
runs layer 1's feed-forward), ``"strict_causal"`` hides each position from
itself. On the module alone (``mtp_logits``): ``"mtp_swapped_halves"`` feeds
``[N_e(emb) ; N_h(h)]``, ``"mtp_no_input_norms"`` drops ``N_h`` and ``N_e``,
``"mtp_shared_final_norm"`` norms with the stack's ``ln_f``, ``"mtp_rope"``
gives the module's block rotary embeddings, ``"mtp_post_final_norm_hidden"``
feeds the stack's hidden state AFTER its final norm.
``"fp8_weights"`` is the control for precision, not a fault: every matrix (the
expert kernels too) rounded to ``float8_e4m3fn``, the nearest precision below
the stated bf16.
"""

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
FAULTS = ("no_window", "rope_on_global", "no_rope_on_window", "no_qk_norm", "qk_norm_whole_width",
          "post_norm", "softmax_router", "no_routed_scaling", "no_topk_renorm", "no_shared_expert",
          "first_layer_sparse", "strict_causal")
MTP_FAULTS = ("mtp_swapped_halves", "mtp_no_input_norms", "mtp_shared_final_norm", "mtp_rope",
              "mtp_post_final_norm_hidden")
# not a fault of the mathematics but the control for precision
PRECISION_CONTROL = "fp8_weights"
Q_BLOCK = 256  # query rows a block of attention: [rows, heads, 256, T] float32 scores


def _up(tree, fault=None):
    def up(x):
        if fault == PRECISION_CONTROL and x.ndim >= 2:
            x = jnp.asarray(x, F32).astype(jnp.float8_e4m3fn)
        return jnp.asarray(x, F32)

    return jax.tree_util.tree_map(up, tree)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotary(x, positions, theta):
    """x [B, T, H, D]; pairs are (i, i + D/2)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions[..., None].astype(F32) * inv_freq  # [B, T, D/2]
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, mask, window, strict):
    """q [B, T, H, D] over k, v [B, T, KV, D], a block of query rows at a time."""
    b, t, heads, d = q.shape
    rep = heads // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    n_blocks = -(-t // Q_BLOCK)
    q = jnp.pad(q, ((0, 0), (0, n_blocks * Q_BLOCK - t), (0, 0), (0, 0)))
    ki = jnp.arange(t)[None, :]

    def block(i):
        rows = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, axis=1)
        qi = (i * Q_BLOCK + jnp.arange(Q_BLOCK))[:, None]
        visible = (ki < qi) if strict else (ki <= qi)
        if window:
            visible = visible & (qi - ki < window)
        visible = visible[None, None] & (mask[:, None, None, :] > 0)
        scores = jnp.einsum("bqhd,bkhd->bhqk", rows, k) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(visible, scores, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    out = jax.lax.map(block, jnp.arange(n_blocks))  # [blocks, B, Q_BLOCK, H, D]
    return jnp.moveaxis(out, 0, 1).reshape(b, n_blocks * Q_BLOCK, heads, d)[:, :t]


def _proj(p, x, lora_alpha):
    """``x W``, plus the low-rank adapter ``(alpha / r) x A B`` where the
    projection carries one (``model.peft_kwargs``)."""
    y = x @ p["kernel"]
    if "lora_a" in p:
        y = y + (lora_alpha / p["lora_a"].shape[1]) * ((x @ p["lora_a"]) @ p["lora_b"])
    return y


def attention(p, h, mask, positions, *, heads, kv_heads, head_dim, eps, theta, window, rotary,
              lora_alpha=16.0, fault=None):
    """``Attn(h)`` of one layer's ``attn`` subtree (float32)."""
    b, t, _ = h.shape
    q, k = _proj(p["q_proj"], h, lora_alpha), _proj(p["k_proj"], h, lora_alpha)
    v = _proj(p["v_proj"], h, lora_alpha).reshape(b, t, kv_heads, head_dim)
    if fault == "qk_norm_whole_width":
        q = _rms_norm(q, jnp.tile(p["q_norm"]["scale"], heads), eps)
        k = _rms_norm(k, jnp.tile(p["k_norm"]["scale"], kv_heads), eps)
    q, k = q.reshape(b, t, heads, head_dim), k.reshape(b, t, kv_heads, head_dim)
    if fault not in ("no_qk_norm", "qk_norm_whole_width"):
        q, k = _rms_norm(q, p["q_norm"]["scale"], eps), _rms_norm(k, p["k_norm"]["scale"], eps)
    if rotary:
        q, k = _rotary(q, positions, theta), _rotary(k, positions, theta)
    out = _attention(q, k, v, mask, window, fault == "strict_causal")
    return _proj(p["o_proj"], out.reshape(b, t, heads * head_dim), lora_alpha)


def _swiglu(p, n):
    return (jax.nn.silu(n @ p["gate_proj"]["kernel"]) * (n @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]


def gates(router_logits, top_k, scaling, fault=None):
    """The dense gate matrix ``[..., router width]``: the sigmoid of each
    logit, kept at each token's ``top_k`` largest, those divided by their sum
    and multiplied by ``scaling``, zero elsewhere."""
    if fault == "softmax_router":
        scores = jax.nn.softmax(router_logits, axis=-1)
    else:
        scores = jax.nn.sigmoid(router_logits)
    kth = jnp.sort(scores, axis=-1)[..., -top_k][..., None]
    g = jnp.where(scores >= kth, scores, 0.0)
    if fault != "no_topk_renorm":
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    return g if fault == "no_routed_scaling" else g * scaling


def _routed(p, n, g, first):
    """The held experts' part: expert ``first + e`` on every token, plainly."""
    y = jnp.zeros_like(n)
    for e in range(p["w_up"].shape[0]):
        inner = jax.nn.silu(n @ p["w_gate"][e]) * (n @ p["w_up"][e])
        y = y + g[..., first + e : first + e + 1] * (inner @ p["w_down"][e])
    return y


def moe_layer(mlp, n, top_k, scaling, first=0, fault=None):
    """One sparse layer alone, in float32: ``(routed, shared)``, the part of
    ``sum_e g_e E_e(n)`` that the experts held in ``mlp`` (``[first, first +
    held)`` of the router's width) give, and ``S(n)``."""
    with jax.default_matmul_precision("highest"):
        p = _up(mlp)
        n = jnp.asarray(n, F32)
        g = gates(n @ p["router"]["kernel"], top_k, scaling, fault)
        return _routed(p, n, g, first), _swiglu(p["shared_expert"], n)


def _ffn(mlp, n, top_k, scaling, first, fault):
    if "router" not in mlp:
        return _swiglu(mlp, n)
    y = _routed(mlp, n, gates(n @ mlp["router"]["kernel"], top_k, scaling, fault), first)
    return y if fault == "no_shared_expert" else y + _swiglu(mlp["shared_expert"], n)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "eps", "theta", "top_k", "scaling", "first", "window", "rotary",
    "lora_alpha", "fault"))
def _layer(layer, mlp, x, mask, positions, *, heads, kv_heads, head_dim, eps, theta, top_k, scaling, first,
           window, rotary, lora_alpha=16.0, fault=None):
    """One block: ``layer``'s norms and attention, ``mlp`` its feed-forward
    (another layer's under the fault ``first_layer_sparse``)."""
    with jax.default_matmul_precision("highest"):
        p, mlp = _up(layer, fault), _up(mlp, fault)
        attn = functools.partial(
            attention, p["attn"], mask=mask, positions=positions, heads=heads, kv_heads=kv_heads,
            head_dim=head_dim, eps=eps, theta=theta, window=window, rotary=rotary, lora_alpha=lora_alpha,
            fault=fault)
        if fault == "post_norm":  # a norm on each sublayer's output, none on its input
            a = x + _rms_norm(attn(x), p["ln_attn"]["scale"], eps)
            return a + _rms_norm(_ffn(mlp, a, top_k, scaling, first, fault), p["ln_mlp"]["scale"], eps)
        a = x + attn(_rms_norm(x, p["ln_attn"]["scale"], eps))
        return a + _ffn(mlp, _rms_norm(a, p["ln_mlp"]["scale"], eps), top_k, scaling, first, fault)


@functools.partial(jax.jit, static_argnames=("eps", "fault"))
def _head(ln_f, lm_head, x, *, eps, fault=None):
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, jnp.asarray(ln_f["scale"], F32), eps)
        return h @ _up(lm_head, fault)["kernel"]


def layer_kinds(dims, fault=None):
    """``[(window or None, rotary)]`` a layer, from the published lists."""
    kinds = []
    for l in range(int(dims["num_hidden_layers"])):
        sliding = dims["layer_types"][l] == "sliding_attention"
        window = int(dims["sliding_windows"][l]) if sliding and fault != "no_window" else None
        roped = sliding
        if fault == "rope_on_global" and not sliding:
            roped = True
        if fault == "no_rope_on_window" and sliding:
            roped = False
        kinds.append((window, roped))
    return kinds


def _sizes(dims):
    return dict(
        heads=int(dims["num_attention_heads"]),
        kv_heads=int(dims["num_key_value_heads"]),
        head_dim=int(dims["head_dim"]),
        eps=float(dims["rms_norm_eps"]),
        theta=float(dims["rope_parameters"]["rope_theta"]),
        top_k=int(dims["num_experts_per_tok"]),
        scaling=float(dims["routed_scaling_factor"]),
        first=int(dims.get("moe_first_expert_held", 0)),
        lora_alpha=float(dims.get("lora_alpha", 16.0)),
    )


def hidden(params, dims, input_ids, attention_mask, fault=None):
    """The residual stream ``[B, T, hidden]`` after the last layer, before the
    final norm, in float32."""
    mask = jnp.asarray(attention_mask, jnp.int32)
    positions = jnp.maximum(jnp.cumsum(mask, axis=1) - 1, 0)
    x = _up(params["wte"], fault)["embedding"][jnp.asarray(input_ids)]
    dense = int(dims["first_k_dense_replace"])
    for l, (window, rotary) in enumerate(layer_kinds(dims, fault)):
        mlp = params[f"h_{l}"]["mlp"]
        if fault == "first_layer_sparse" and l < dense:
            mlp = params[f"h_{dense}"]["mlp"]
        x = _layer(params[f"h_{l}"], mlp, x, mask, positions, window=window, rotary=rotary, fault=fault,
                   **_sizes(dims))
    return x


def logits(params, dims, input_ids, attention_mask, span, fault=None):
    """Float32 logits ``[B, span[1] - span[0], vocab]`` of the backbone tree
    ``params`` on ``input_ids`` [B, T] with ``attention_mask`` [B, T]."""
    x = hidden(params, dims, input_ids, attention_mask, fault)
    return _head(params["ln_f"], params["lm_head"], x[:, span[0] : span[1]],
                 eps=float(dims["rms_norm_eps"]), fault=fault)


@functools.partial(jax.jit, static_argnames=("eps", "fault"))
def _mtp_input(module, h, emb, *, eps, fault=None):
    with jax.default_matmul_precision("highest"):
        p = _up(module, fault)
        if fault != "mtp_no_input_norms":
            h, emb = _rms_norm(h, p["h_norm"]["scale"], eps), _rms_norm(emb, p["e_norm"]["scale"], eps)
        halves = [emb, h] if fault == "mtp_swapped_halves" else [h, emb]
        return jnp.concatenate(halves, axis=-1) @ p["eh_proj"]["kernel"]


def mtp_logits(params, dims, input_ids, attention_mask, span, fault=None):
    """Float32 logits of the next-token-prediction module: position ``t`` of
    the result is its distribution over ``x_{t+2}``, from the stack's hidden
    state at ``t`` and the embedding of ``x_{t+1}``. ``span`` cuts positions
    of ``[0, T - 1)``: the last token has no next one. A ``fault`` of
    ``FAULTS`` or the precision control is planted in the stack under the
    module, one of ``MTP_FAULTS`` in the module alone."""
    eps = float(dims["rms_norm_eps"])
    ids, mask = jnp.asarray(input_ids), jnp.asarray(attention_mask, jnp.int32)
    stack_fault = fault if fault not in MTP_FAULTS else None
    x = hidden(params, dims, ids, mask, stack_fault)
    if fault == "mtp_post_final_norm_hidden":
        x = _rms_norm(x, jnp.asarray(params["ln_f"]["scale"], F32), eps)
    module = params["mtp_0"]
    emb = _up(params["wte"], stack_fault)["embedding"][ids[:, 1:]]
    # the module's entry t is valid where token t is (left padding: token t + 1 then is too)
    mask, positions = mask[:, :-1], jnp.maximum(jnp.cumsum(mask, axis=1) - 1, 0)[:, :-1]
    y = _mtp_input(module, x[:, :-1], emb, eps=eps, fault=fault)
    y = _layer(module["block"], module["block"]["mlp"], y, mask, positions, window=None,
               rotary=fault == "mtp_rope", fault=stack_fault, **_sizes(dims))
    ln_f = params["ln_f"] if fault == "mtp_shared_final_norm" else module["ln_f"]
    return _head(ln_f, params["lm_head"], y[:, span[0] : span[1]], eps=eps, fault=stack_fault)
