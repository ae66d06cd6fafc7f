"""GLM-5.2's forward pass, plainly.

Written from the published ``zai-org/GLM-5.2`` ``config.json`` (``model_type``
``glm_moe_dsa``): a decoder of 78 layers with latent attention under a
learned sparse selection of keys, three leading dense layers and 75 layers of
256 routed experts beside one shared expert; RMSNorm (eps 1e-5, learned
scale) before each sublayer and none after, no bias anywhere, an untied head.
``x`` is the residual stream, ``h = N1(x)`` a layer's normed input.

- Layer: ``a = x + Attn(N1(x))``, ``y = a + FFN(N2(a))``.
- Attention: ``cq = RMS(h Wqa)`` (2048); ``q = cq Wqb``: 64 heads of
  ``[q_n(192) | q_r(64)]``; ``[ckv(512) | k_r(64)] = h Wkva``, ``c =
  RMS(ckv)``, ONE ``k_r`` for all heads; rotary embedding (theta 8e6,
  split-half pairs) on ``q_r`` and ``k_r`` only; ``[k_n(192) | v(256)]`` a
  head ``= c Wkvb``; scores ``(q_n . k_n + q_r . k_r) / sqrt(256)``; the
  softmax of query ``t`` runs over the slots ``S_t`` only; ``o = sum p v``,
  output ``concat(o) Wo`` (16384 -> 6144).
- The selection. On a layer whose ``indexer_types`` entry is ``full``:
  ``qI_{t,j} = (cq_t WIq)_j`` for j = 1..32, 128 wide, rope on its first 64
  dims; ``kI_s = LayerNorm(h_s WIk)``, 128 wide, ONE key for the 32 heads,
  rope on its first 64; ``w_t = h_t WIw / sqrt(32 x 128)``; ``I_{t,s} = sum_j
  w_{t,j} relu(qI_{t,j} . kI_s)`` for valid ``s <= t``; ``S_t`` = the
  ``min(index_topk, t + 1)`` valid slots of the largest ``I_{t,.}`` (of equal
  scores the lower slot first). On a ``shared`` layer ``S_t`` is the last
  ``full`` layer's: the layer has no indexer. With ``t < index_topk`` that is
  every causal key.
- Layers ``0 .. first_k_dense_replace - 1``: SwiGLU ``down(silu(gate n) *
  up n)`` at ``intermediate_size``.
- The other layers: ``s = sigmoid(n Wr)`` over the router's 256; the 8
  largest of ``s + b`` (``topk_method`` ``noaux_tc``: the bias chooses and
  weighs nothing; ``n_group`` 1, ``topk_group`` 1: no group limit); ``w = 2.5
  * s_top / sum(s_top)``; ``y = sum_e w_e E_e(n) + S(n)``, ``E_e`` and the
  shared expert ``S`` SwiGLU of ``moe_intermediate_size``; no capacity bound.
- Final RMSNorm, untied head.

Plain ``jax.numpy`` in float32 under ``highest`` matmul precision, one row at
a time and a block of ``QUERY_BLOCK`` queries at a time (four rows of 7296 tokens
fit beside what the trainer holds): EXPANDED attention only (per-head K and V
built from the latent; no cache, no absorption, no kernel), the index scores
of a block as a dense ``[heads, queries, keys]`` array, its own top-k (a
stable sort of each query's scores), the selection a dense boolean ``[T, T]``
carried from a ``full`` layer to the ``shared`` layers behind it, the gate a
dense ``[tokens, router width]`` matrix, every held expert applied to every
token. It walks the system's own parameter tree one layer at a time and casts
that layer up. The sizes come from ``dims``, the published keys of the
configuration file. A projection that carries a LoRA adapter adds ``(alpha /
r) x A B``, ``alpha`` from ``dims["lora_alpha"]``.

**One chip's share.** As ``pangu_ultra_moe.py``: the expert kernels hold
``dims["n_routed_experts"]`` experts, the slice ``[first, first + held)`` of
the router's width; the reference routes over the whole width, renormalises
over all eight chosen, adds only what the held experts give, and the shared
expert whole. ``moe_layer`` is that one layer alone, routed part and shared
part apart, for the test that the shares add up to the uncut layer.

Departures from the publication, all of them: (1) left padding gets
positions ``cumsum(mask) - 1`` and is never selected. (2) The
next-token-prediction module (``num_nextn_predict_layers`` 1) is not built,
so ``index_share_for_mtp_iteration`` reads nothing. (3) The published code
rotates ``qI`` and ``kI`` by a Hadamard matrix and keeps the index keys in
fp8: an orthogonal map applied to both sides of a dot product, and a storage
format; neither is built. (4) ``rope_interleave`` and
``indexer_rope_interleave`` are relabellings of columns under random weights:
split-half pairs ``(i, i + 32)`` everywhere. (5) The index key's LayerNorm
takes eps 1e-6 (the published layer's own default; the config names none).

``fault`` plants a known error for the yardstick's control run:
``"dense_attention"`` drops the selection (every causal key),
``"own_window"`` gives a ``shared`` layer its last ``index_topk`` slots in
place of the borrowed set, ``"no_index_relu"`` sums the index heads without
the ReLU, ``"half_topk"`` selects ``index_topk / 2``,
``"no_selection_bias"`` chooses experts by their scores alone,
``"softmax_router"`` scores with a softmax over the experts,
``"no_routed_scaling"`` drops the 2.5, ``"no_shared_expert"`` the shared
expert. ``"fp8_weights"`` is the control for precision, not a fault: every
matrix (the expert kernels too) rounded to ``float8_e4m3fn``, the nearest
precision below the stated bf16.
"""

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
FAULTS = ("dense_attention", "own_window", "no_index_relu", "half_topk", "no_selection_bias",
          "softmax_router", "no_routed_scaling", "no_shared_expert")
# not a fault of the mathematics but the control for precision
PRECISION_CONTROL = "fp8_weights"
# query rows a block: [64 heads, 256, T] float32 scores, [32, 256, T] index products; a row
# shorter than that is one block of its own length (no padded queries)
QUERY_BLOCK = 256
INDEX_NORM_EPS = 1e-6


def _up(tree, fault=None):
    def up(x):
        if fault == PRECISION_CONTROL and x.ndim >= 2:
            x = jnp.asarray(x, F32).astype(jnp.float8_e4m3fn)
        return jnp.asarray(x, F32)

    return jax.tree_util.tree_map(up, tree)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rotary(x, positions, theta, dim):
    """x [T, H, D]: the first ``dim`` columns rotated, pairs ``(i, i + dim/2)``."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim))
    ang = positions[:, None].astype(F32) * inv_freq  # [T, dim/2]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2, rest = x[..., : dim // 2], x[..., dim // 2 : dim], x[..., dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _proj(p, x, lora_alpha):
    """``x W``, plus the low-rank adapter ``(alpha / r) x A B`` where the
    projection carries one (``model.peft_kwargs``)."""
    y = x @ p["kernel"]
    if "lora_a" in p:
        y = y + (lora_alpha / p["lora_a"].shape[1]) * ((x @ p["lora_a"]) @ p["lora_b"])
    return y


def top_k_mask(scores, visible, k):
    """Its own top-k: ``[Q, T]`` bool, for each query the ``k`` visible keys
    of the largest score (all of them where there are fewer), of equal scores
    the lower slot first: a stable sort of each query's scores, descending."""
    order = jnp.argsort(-jnp.where(visible, scores, -jnp.inf), axis=-1, stable=True)
    rows = jnp.arange(scores.shape[0])[:, None]
    chosen = jnp.zeros(scores.shape, bool).at[rows, order[:, : min(k, scores.shape[1])]].set(True)
    return chosen & visible


def index_scores(p, cq, h, positions, *, heads, dim, rope, theta, fault=None):
    """``(qI [T, HI, DI], kI [T, DI], w [T, HI])`` of one row from a ``full``
    layer's ``indexer`` subtree; ``I = sum_j w_j relu(qI_j . kI)`` is formed a
    block of queries at a time by the caller."""
    q_i = _rotary((cq @ p["wq_b"]["kernel"]).reshape(-1, heads, dim), positions, theta, rope)
    k_i = _layer_norm(h @ p["wk"]["kernel"], p["k_norm"], INDEX_NORM_EPS)
    k_i = _rotary(k_i[:, None, :], positions, theta, rope)[:, 0]
    w = (h @ p["weights_proj"]["kernel"]) / math.sqrt(heads * dim)
    return q_i, k_i, w


def sparse_attention(p, h, mask, positions, borrowed, *, heads, nope, rope, v_dim, eps, theta, topk,
                     index_heads, index_dim, lora_alpha=16.0, fault=None):
    """``(Attn(h), selection)`` of ONE row ``h [T, hidden]`` from one layer's
    ``attn`` subtree (float32), expanded; ``selection [T, T]`` is the set in
    force here: made by this layer's indexer where it has one, ``borrowed``
    where it has none."""
    t = h.shape[0]
    r = p["kv_a_norm"]["scale"].shape[0]
    cq = _rms_norm(_proj(p["q_a_proj"], h, lora_alpha), p["q_a_norm"]["scale"], eps)
    kv_a = _proj(p["kv_a_proj"], h, lora_alpha)
    c = _rms_norm(kv_a[:, :r], p["kv_a_norm"]["scale"], eps)
    q = _proj(p["q_b_proj"], cq, lora_alpha).reshape(t, heads, nope + rope)
    kv = (c @ p["kv_b_proj"]["kernel"]).reshape(t, heads, nope + v_dim)
    k_r = _rotary(kv_a[:, None, r:], positions, theta, rope)
    q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], positions, theta, rope)], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r, (t, heads, rope))], axis=-1)
    v = kv[..., nope:]
    own = "indexer" in p
    if own:
        q_i, k_i, w = index_scores(p["indexer"], cq, h, positions, heads=index_heads, dim=index_dim,
                                   rope=rope, theta=theta, fault=fault)
    k_sel = topk // 2 if fault == "half_topk" else topk

    q_block = min(QUERY_BLOCK, t)
    n_blocks = -(-t // q_block)
    pad = n_blocks * q_block - t
    padded = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    q_p = padded(q)
    if own:
        q_i, w = padded(q_i), padded(w)
    else:
        borrowed = padded(borrowed)
    ki = jnp.arange(t)[None, :]

    def block(i):
        rows = lambda a: jax.lax.dynamic_slice_in_dim(a, i * q_block, q_block, axis=0)
        qi = (i * q_block + jnp.arange(q_block))[:, None]
        visible = (ki <= qi) & (mask[None, :] > 0)
        if fault == "dense_attention":
            chosen = visible
        elif own:
            dots = jnp.einsum("qhd,kd->hqk", rows(q_i), k_i)
            if fault != "no_index_relu":
                dots = jax.nn.relu(dots)
            chosen = top_k_mask(jnp.einsum("hqk,qh->qk", dots, rows(w)), visible, k_sel)
        elif fault == "own_window":
            chosen = visible & (qi - ki < k_sel)
        else:
            chosen = rows(borrowed)
        scores = jnp.einsum("qhd,khd->hqk", rows(q_p), k) / math.sqrt(nope + rope)
        probs = jax.nn.softmax(jnp.where((chosen & visible)[None], scores, -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v), chosen

    out, chosen = jax.lax.map(block, jnp.arange(n_blocks))
    out = out.reshape(n_blocks * q_block, heads * v_dim)[:t]
    return _proj(p["o_proj"], out, lora_alpha), chosen.reshape(n_blocks * q_block, t)[:t]


def _swiglu(p, n):
    return (jax.nn.silu(n @ p["gate_proj"]["kernel"]) * (n @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]


def gates(router_logits, bias, top_k, scaling, fault=None):
    """The dense gate matrix ``[..., router width]``: the sigmoid of each
    logit, kept at each token's ``top_k`` largest of ``score + bias``, those
    SCORES divided by their sum and multiplied by ``scaling``, zero elsewhere."""
    if fault == "softmax_router":
        scores = jax.nn.softmax(router_logits, axis=-1)
    else:
        scores = jax.nn.sigmoid(router_logits)
    choosing = scores if fault == "no_selection_bias" else scores + bias
    kth = jnp.sort(choosing, axis=-1)[..., -top_k][..., None]
    g = jnp.where(choosing >= kth, scores, 0.0)
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
    ``sum_e w_e E_e(n)`` that the experts held in ``mlp`` (``[first, first +
    held)`` of the router's width) give, and ``S(n)``."""
    with jax.default_matmul_precision("highest"):
        p = _up(mlp)
        n = jnp.asarray(n, F32)
        g = gates(n @ p["router"]["kernel"], p["router_bias"], top_k, scaling, fault)
        return _routed(p, n, g, first), _swiglu(p["shared_expert"], n)


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope", "v_dim", "eps", "theta", "topk", "index_heads", "index_dim", "top_k",
    "scaling", "first", "lora_alpha", "fault"))
def _layer(layer, x, mask, positions, borrowed, *, heads, nope, rope, v_dim, eps, theta, topk,
           index_heads, index_dim, top_k, scaling, first, lora_alpha=16.0, fault=None):
    """One row ``x [T, hidden]`` through one layer: ``(y, selection)``."""
    with jax.default_matmul_precision("highest"):
        p = _up(layer, fault)
        attn, selection = sparse_attention(
            p["attn"], _rms_norm(x, p["ln_attn"]["scale"], eps), mask, positions, borrowed,
            heads=heads, nope=nope, rope=rope, v_dim=v_dim, eps=eps, theta=theta, topk=topk,
            index_heads=index_heads, index_dim=index_dim, lora_alpha=lora_alpha, fault=fault)
        a = x + attn
        n = _rms_norm(a, p["ln_mlp"]["scale"], eps)
        mlp = p["mlp"]
        if "router" not in mlp:  # a leading dense layer
            y = _swiglu(mlp, n)
        else:
            g = gates(n @ mlp["router"]["kernel"], mlp["router_bias"], top_k, scaling, fault)
            y = _routed(mlp, n, g, first)
            if fault != "no_shared_expert":
                y = y + _swiglu(mlp["shared_expert"], n)
        return a + y, selection


@functools.partial(jax.jit, static_argnames=("eps", "fault"))
def _head(ln_f, lm_head, x, *, eps, fault=None):
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, jnp.asarray(ln_f["scale"], F32), eps)
        return h @ _up(lm_head, fault)["kernel"]


def hidden(params, dims, input_ids, attention_mask, fault=None):
    """The residual stream ``[B, T, hidden]`` after the last layer, in
    float32, one row after another."""
    mask = jnp.asarray(attention_mask, jnp.int32)
    positions = jnp.maximum(jnp.cumsum(mask, axis=1) - 1, 0)
    embedding = _up(params["wte"], fault)["embedding"]
    types = list(dims["indexer_types"])
    statics = dict(
        heads=int(dims["num_attention_heads"]),
        nope=int(dims["qk_nope_head_dim"]),
        rope=int(dims["qk_rope_head_dim"]),
        v_dim=int(dims["v_head_dim"]),
        eps=float(dims["rms_norm_eps"]),
        theta=float(dims["rope_parameters"]["rope_theta"]),
        topk=int(dims["index_topk"]),
        index_heads=int(dims["index_n_heads"]),
        index_dim=int(dims["index_head_dim"]),
        top_k=int(dims["num_experts_per_tok"]),
        scaling=float(dims["routed_scaling_factor"]),
        first=int(dims.get("moe_first_expert_held", 0)),
        lora_alpha=float(dims.get("lora_alpha", 16.0)),
        fault=fault,
    )
    rows = []
    for b in range(mask.shape[0]):
        x = embedding[jnp.asarray(input_ids)[b]]
        selection = None
        for l in range(int(dims["num_hidden_layers"])):
            layer = params[f"h_{l}"]
            if (types[l] == "full") != ("indexer" in layer["attn"]):
                raise ValueError(f"layer {l}: indexer_types says {types[l]!r}, the tree "
                                 f"{'has' if 'indexer' in layer['attn'] else 'lacks'} an indexer")
            x, selection = _layer(layer, x, mask[b], positions[b], selection, **statics)
        rows.append(x)
    return jnp.stack(rows)


def logits(params, dims, input_ids, attention_mask, span, fault=None):
    """Float32 logits ``[B, span[1] - span[0], vocab]`` of the backbone tree
    ``params`` on ``input_ids`` [B, T] with ``attention_mask`` [B, T]."""
    x = hidden(params, dims, input_ids, attention_mask, fault)
    return _head(params["ln_f"], params["lm_head"], x[:, span[0] : span[1]],
                 eps=float(dims["rms_norm_eps"]), fault=fault)
