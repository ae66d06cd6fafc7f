"""SmallThinker-21BA3B-Instruct's forward pass, plainly.

Written from the published ``PowerInfer/SmallThinker-21BA3B-Instruct``
``config.json`` and the family's description (Song et al. 2025, "SmallThinker:
A Family of Efficient Large Language Models Natively Trained for Local
Deployment"): a pre-norm decoder of 52 blocks, RMSNorm (eps 1e-6, learned
scale), no bias anywhere, an untied head. ``x`` is the residual stream.

- Block ``l``: ``r = x W_r`` are the router's 64 logits, read from the block's
  INPUT, before the input norm and before attention;
  ``h = x + Attn_l(RMSNorm_in(x))``; ``y = h + MoE(RMSNorm_post(h); r)``.
- Attention: q, k, v without bias; 28 query heads, 4 key/value heads (query
  heads ``7j .. 7j+6`` share key/value head ``j``), head size 128, scale
  ``1/sqrt(128)``, causal. The layer's kind comes from the two published
  lists, each read on its own: where ``sliding_window_layout[l]`` is 1 a
  query sees its last ``sliding_window_size`` positions, itself included;
  where ``rope_layout[l]`` is 1 q and k get rotary embeddings over the whole
  head (split-half pairing, theta 1,500,000). As published both are 1 in
  layers 1, 2, 3 of every four and both 0 in layers 0, 4, 8, ...: those
  attend over everything before them with NO positional encoding at all.
- MoE: ``p = softmax(r)`` over the 64; the six largest, divided by their sum
  (``norm_topk_prob`` and ``moe_primary_router_apply_softmax`` both true);
  expert ``e`` is ``down_e(relu(gate_e n) * up_e n)`` of width 768 on ``n =
  RMSNorm_post(h)``; no shared expert, no capacity bound, no dense layer.

Plain ``jax.numpy`` in float32 under ``highest`` matmul precision: no kernel,
no cache, no ring, no sort, no grouped matmul. The gate is a dense ``[tokens,
router width]`` matrix, zero outside each token's six, and every held expert
is applied to every token, one expert at a time. Attention is computed a
block of ``Q_BLOCK`` query rows at a time against all keys, so that 8192
positions fit beside a trainer. It walks the system's own parameter tree one
layer at a time and casts that layer up. The sizes come from ``dims``, the
published keys of the configuration file.

**One chip's share.** The expert kernels of the tree hold
``dims["moe_num_primary_experts"]`` experts, the slice ``[first, first +
held)`` of the router's width (``first`` is ``dims["moe_first_expert_held"]``,
0 if absent; the width is the router kernel's). The reference is given the
same share as the program: it routes over the whole width, renormalises over
all six chosen, and adds only what the held experts give; what the absent
ones would have added is left out, and that partial result goes on to the
next layer. ``moe_layer`` is that one layer alone, for the test that the four
shares add up to the uncut layer.

Departures from the publication, all of them: (1) left padding gets positions
``cumsum(mask) - 1`` (what a Hugging Face user passes as ``position_ids`` for
a left-padded batch); the window is on slot distance, which is position
distance because padding is left-only. (2) The activation-sparsity predictor
inside an expert ("secondary experts" in the family's description) is not
modelled: this ``config.json`` has no key of it. Three readings the config
does not settle are taken as the configuration file's ``assumed`` says, each
with its other reading as a planted fault below: the router reads the raw
block input (llama.cpp computes ``ffn_moe_logits`` from ``inpL``); the
experts are ReGLU (the family's description; the config has no
``hidden_act``); rotary pairs are split-half.

``fault`` plants a known error for the yardstick's control run:
``"no_window"`` lets the window layers see everything before them,
``"rope_on_global"`` gives the global layers rotary embeddings,
``"no_rope_on_window"`` takes them from the window layers,
``"router_after_input_norm"`` and ``"router_after_attention"`` move the
router's input (the second is OLMoE's place: the normed input of the experts),
``"silu_gate"`` makes the experts SwiGLU, ``"no_topk_renorm"`` uses the six
probabilities as they are, ``"top5"`` drops each token's sixth expert,
``"strict_causal"`` hides each position from itself. ``"fp8_weights"`` is the
control for precision, not a fault: every matrix (the expert kernels too)
rounded to ``float8_e4m3fn``, the nearest precision below the stated bf16.
"""

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
FAULTS = ("no_window", "rope_on_global", "no_rope_on_window", "router_after_input_norm",
          "router_after_attention", "silu_gate", "no_topk_renorm", "top5", "strict_causal")
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


def gates(router_logits, top_k, fault=None):
    """The dense gate matrix ``[..., router width]``: softmax of the router's
    logits, kept at each token's ``top_k`` largest entries, those divided by
    their sum, zero elsewhere."""
    probs = jax.nn.softmax(router_logits, axis=-1)
    keep = top_k - 1 if fault == "top5" else top_k
    kth = jnp.sort(probs, axis=-1)[..., -keep][..., None]
    g = jnp.where(probs >= kth, probs, 0.0)
    if fault != "no_topk_renorm":
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    return g


def _experts(p, n, g, first, fault):
    """The held experts' part: expert ``first + e`` on every token, plainly."""
    act = jax.nn.silu if fault == "silu_gate" else jax.nn.relu
    y = jnp.zeros_like(n)
    for e in range(p["w_up"].shape[0]):
        inner = act(n @ p["w_gate"][e]) * (n @ p["w_up"][e])
        y = y + g[..., first + e : first + e + 1] * (inner @ p["w_down"][e])
    return y


def moe_layer(mlp, n, router_input, top_k, first=0, fault=None):
    """One sparse layer alone, in float32: the part of ``MoE(n; r)`` that the
    experts held in ``mlp`` (``[first, first + held)`` of the router's width)
    give, with ``r = router_input @ W_r``."""
    with jax.default_matmul_precision("highest"):
        p = _up(mlp)
        g = gates(jnp.asarray(router_input, F32) @ p["router"]["kernel"], top_k, fault)
        return _experts(p, jnp.asarray(n, F32), g, first, fault)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "eps", "theta", "top_k", "first", "window", "rotary", "fault"))
def _layer(layer, x, mask, positions, *, heads, kv_heads, head_dim, eps, theta, top_k, first,
           window, rotary, fault=None):
    with jax.default_matmul_precision("highest"):
        p = _up(layer, fault)
        b, t, _ = x.shape
        h = _rms_norm(x, p["ln_attn"]["scale"], eps)
        q = (h @ p["attn"]["q_proj"]["kernel"]).reshape(b, t, heads, head_dim)
        k = (h @ p["attn"]["k_proj"]["kernel"]).reshape(b, t, kv_heads, head_dim)
        v = (h @ p["attn"]["v_proj"]["kernel"]).reshape(b, t, kv_heads, head_dim)
        if rotary:
            q, k = _rotary(q, positions, theta), _rotary(k, positions, theta)
        attn = _attention(q, k, v, mask, window, fault == "strict_causal")
        after = x + attn.reshape(b, t, heads * head_dim) @ p["attn"]["o_proj"]["kernel"]
        n = _rms_norm(after, p["ln_mlp"]["scale"], eps)
        router_input = {"router_after_input_norm": h, "router_after_attention": n}.get(fault, x)
        g = gates(router_input @ p["mlp"]["router"]["kernel"], top_k, fault)
        return after + _experts(p["mlp"], n, g, first, fault)


@functools.partial(jax.jit, static_argnames=("eps", "fault"))
def _head(ln_f, lm_head, x, *, eps, fault=None):
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, jnp.asarray(ln_f["scale"], F32), eps)
        return h @ _up(lm_head, fault)["kernel"]


def layer_kinds(dims, fault=None):
    """``[(window or None, rotary)]`` a layer, from the two published lists,
    each read on its own."""
    kinds = []
    for l in range(int(dims["num_hidden_layers"])):
        windowed = bool(dims["sliding_window_layout"][l]) and fault != "no_window"
        roped = bool(dims["rope_layout"][l])
        if fault == "rope_on_global" and not dims["sliding_window_layout"][l]:
            roped = True
        if fault == "no_rope_on_window" and dims["sliding_window_layout"][l]:
            roped = False
        kinds.append((int(dims["sliding_window_size"]) if windowed else None, roped))
    return kinds


def hidden(params, dims, input_ids, attention_mask, fault=None):
    """The residual stream ``[B, T, hidden]`` after the last layer, in
    float32."""
    mask = jnp.asarray(attention_mask, jnp.int32)
    positions = jnp.maximum(jnp.cumsum(mask, axis=1) - 1, 0)
    x = _up(params["wte"], fault)["embedding"][jnp.asarray(input_ids)]
    for l, (window, rotary) in enumerate(layer_kinds(dims, fault)):
        x = _layer(
            params[f"h_{l}"], x, mask, positions,
            heads=int(dims["num_attention_heads"]),
            kv_heads=int(dims["num_key_value_heads"]),
            head_dim=int(dims["head_dim"]),
            eps=float(dims["rms_norm_eps"]),
            theta=float(dims["rope_theta"]),
            top_k=int(dims["moe_num_active_primary_experts"]),
            first=int(dims.get("moe_first_expert_held", 0)),
            window=window, rotary=rotary, fault=fault,
        )
    return x


def logits(params, dims, input_ids, attention_mask, span, fault=None):
    """Float32 logits ``[B, span[1] - span[0], vocab]`` of the backbone tree
    ``params`` on ``input_ids`` [B, T] with ``attention_mask`` [B, T]."""
    x = hidden(params, dims, input_ids, attention_mask, fault)
    return _head(params["ln_f"], params["lm_head"], x[:, span[0] : span[1]],
                 eps=float(dims["rms_norm_eps"]), fault=fault)
