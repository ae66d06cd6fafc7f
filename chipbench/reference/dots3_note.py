"""dots3-note-prev's forward pass, plainly.

Written from the published ``dots-studio/dots3-note-prev`` ``config.json``
(``model_type`` ``dots3_note``; the language model's settings: the vision and
audio towers and the next-token module are outside it): a decoder of 46 layers
whose attention is latent attention of TWO geometries, by ``layer_types``: a
``full_attention`` layer (13 of them) under a learned sparse selection of
keys, a ``sliding_attention`` layer (33) inside a window of
``sliding_window_size`` at the ``swa_*`` sizes; one leading dense layer and 45
layers of 256 routed experts beside one shared expert; RMSNorm (eps 1e-5,
learned scale) before each sublayer and none after, no bias anywhere, an
untied head. ``x`` is the residual stream, ``u = N1(x)`` a layer's normed input.

- Layer: ``a = x + Attn(N1(x))``, ``y = a + FFN(N2(a))``.
- Attention of a layer of kind ``(H, rq, r, dn, dr, dv, theta, w)`` (full: 128,
  1024, 512, 128, 64, 128, 8e7, none; sliding: 64, 1024, 1024, 192, 64, 128,
  5e4, 513): ``cq = s_q RMS(u Wqa)``, ``s_q = sqrt(hidden / rq)``; ``q = cq
  Wqb``: ``H`` heads of ``[q_n(dn) | q_r(dr)]``; ``[ckv(r) | k_r(dr)] = u
  Wkva``, ``c = s_kv RMS(ckv)``, ``s_kv = sqrt(hidden / r)``
  (``apply_mla_qkv_lora_rescale``), ONE ``k_r`` for all heads, neither normed
  nor scaled; rotary embedding (base ``theta``, split-half pairs) on ``q_r`` and
  ``k_r`` only; ``[k_n(dn) | v(dv)]`` a head ``= c Wkvb``; scores ``(q_n . k_n
  + q_r . k_r) / sqrt(dn + dr)``; the softmax of query ``t`` runs over the
  visible slots: sliding ``s <= t``, ``t - s < w`` (the query's own slot
  counts: ``w`` keys), not padded; full ``s`` in ``S_t``; ``o_h = sum p v``;
  gate ``g = sigmoid(u Wgate)``, one number a head (``attention_gate_type``
  headwise); output ``Wo concat_h(g_h o_h)``.
- The selection, on EVERY full layer (the config lists no borrowing):
  ``qI_{t,j} = (cq_t WIq)_j`` for j = 1..64, 128 wide, rope (the full layers'
  base) on its first 64 dims; ``kI_s = LayerNorm(u_s WIk)``, 128 wide, ONE key
  for the 64 heads, rope on its first 64; ``w_t = u_t WIw / sqrt(64 x 128)``;
  ``I_{t,s} = sum_j w_{t,j} relu(qI_{t,j} . kI_s)`` for valid ``s <= t``;
  ``S_t`` = the ``min(index_topk, t + 1)`` valid slots of the largest
  ``I_{t,.}`` (of equal scores the lower slot first). ``I`` is positively
  homogeneous in ``qI``, so whether ``qI`` reads the scaled ``cq`` cannot be
  observed; it reads the scaled one here.
- Layer 0: SwiGLU ``down(silu(gate n) * up n)`` at ``intermediate_size``.
- The other layers: ``s = sigmoid(n Wr)`` over the router's 256; the 8
  largest of ``s + b`` (``topk_method`` ``noaux_tc``; no group limit: the
  config has no ``n_group``); ``w = 1 * s_top / sum(s_top)``; ``y = sum_e w_e
  E_e(n) + S(n)``, ``E_e`` and the shared expert ``S`` SwiGLU of
  ``moe_intermediate_size``; no capacity bound.
- Final RMSNorm, untied head.

Plain ``jax.numpy`` in float32 under ``highest`` matmul precision, one row at
a time and a block of ``QUERY_BLOCK`` queries at a time: EXPANDED attention
only in BOTH kinds of layer (per-head K and V built from the latent; no cache,
no ring, no absorption, no kernel), the window a dense boolean mask, the index
scores of a block a dense ``[heads, queries, keys]`` array, its own top-k (a
stable sort), the gate a dense ``[tokens, router width]`` matrix, every held
expert applied to every token. It walks the system's own parameter tree one
layer at a time and casts that layer up; a layer's sizes come from ``dims``,
the published keys of the configuration file (the ``swa_*`` ones on a
``sliding_attention`` layer), never from the tree. A projection that carries
a LoRA adapter adds ``(alpha / r) x A B``.

**One chip's share.** As ``glm_moe_dsa.py``: the expert kernels hold
``dims["n_routed_experts"]`` experts, the slice ``[first, first + held)`` of
the router's width; the reference routes over the whole width, renormalises
over all eight chosen, adds only what the held experts give, and the shared
expert whole. ``moe_layer`` is that one layer alone, for the test that the
shares add up to the uncut layer.

Departures from the publication, all of them: (1) left padding gets positions
``cumsum(mask) - 1`` and is never attended to or selected. (2) The towers and
the next-token module are not built. (3) Split-half rotary pairs everywhere
(a relabelling of columns under random weights). (4) The index key's
LayerNorm takes eps 1e-6 (the config names none).

``fault`` plants a known error for the yardstick's control run. The two
``assumed`` mechanisms' other readings: ``"no_gate"`` drops the headwise gate,
``"no_lora_rescale"`` the two latent scales. The two geometries:
``"one_theta"`` ropes the sliding layers at the full layers' base,
``"window_as_full"`` gives a sliding layer every causal key (no window: what
tells this model from one whose layers all attend alike). The selection, as
``glm_moe_dsa.py`` plants them: ``"dense_attention"`` (every causal key on a
full layer), ``"half_topk"``, ``"no_index_relu"``. The router:
``"no_selection_bias"``, ``"softmax_router"``, ``"no_shared_expert"``.
``"fp8_weights"`` is the control for precision, not a fault: every matrix
(the expert kernels too) rounded to ``float8_e4m3fn``, the nearest precision
below the stated bf16 parameters. ``"bf16_softmax"`` is a second one: the
attention scores and the softmax's weights rounded to bfloat16 where the
configuration says float32; on the chip it reads inside the clean seeds' band
(``chipbench/tolerances/dots3-note-prev-l6e8.json``), so it decides nothing.
"""

import functools
import math
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
FAULTS = ("no_gate", "no_lora_rescale", "one_theta", "window_as_full", "dense_attention", "half_topk",
          "no_index_relu", "no_selection_bias", "softmax_router", "no_shared_expert")
# not faults of the mathematics but the controls for precision: the one that decides, and a second
PRECISION_CONTROL = "fp8_weights"
SOFTMAX_PRECISION_CONTROL = "bf16_softmax"
# query rows a block: [128 heads, 128, T] float32 scores, [64, 128, T] index products; a row
# shorter than that is one block of its own length (no padded queries)
QUERY_BLOCK = 128
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


class Kind(NamedTuple):
    """One kind of layer's attention sizes, from the published keys."""

    heads: int
    nope: int
    rope: int
    v_dim: int
    theta: float
    window: Optional[int]  # None: a full layer, under the selection


def kinds(dims) -> Dict[str, Kind]:
    """``layer_types`` entry -> sizes: the top-level keys on a full layer, the
    ``swa_*`` keys and ``sliding_window_size`` on a sliding one."""
    return {
        "full_attention": Kind(int(dims["num_attention_heads"]), int(dims["qk_nope_head_dim"]), int(dims["qk_rope_head_dim"]),
                               int(dims["v_head_dim"]), float(dims["rope_theta"]), None),
        "sliding_attention": Kind(int(dims["swa_num_attention_heads"]), int(dims["swa_qk_nope_head_dim"]),
                                  int(dims["swa_qk_rope_head_dim"]), int(dims["swa_v_head_dim"]), float(dims["swa_rope_theta"]),
                                  int(dims["sliding_window_size"])),
    }


def attention(p, u, mask, positions, kind: Kind, *, hidden, eps, topk, index_heads, index_dim, index_theta,
              gated=True, rescale=True, lora_alpha=16.0, fault=None):
    """``Attn(u)`` of ONE row ``u [T, hidden]`` from one layer's ``attn``
    subtree (float32), expanded, at the layer's ``kind``: under the window on
    a sliding layer, under its own indexer's selection on a full one."""
    t = u.shape[0]
    heads, nope, rope, v_dim = kind.heads, kind.nope, kind.rope, kind.v_dim
    theta = index_theta if (fault == "one_theta" and kind.window) else kind.theta
    window = None if fault == "window_as_full" else kind.window
    rq, r = p["q_a_norm"]["scale"].shape[0], p["kv_a_norm"]["scale"].shape[0]
    scaled = rescale and fault != "no_lora_rescale"
    cq = _rms_norm(_proj(p["q_a_proj"], u, lora_alpha), p["q_a_norm"]["scale"], eps) * (math.sqrt(hidden / rq) if scaled else 1.0)
    kv_a = _proj(p["kv_a_proj"], u, lora_alpha)
    c = _rms_norm(kv_a[:, :r], p["kv_a_norm"]["scale"], eps) * (math.sqrt(hidden / r) if scaled else 1.0)
    q = _proj(p["q_b_proj"], cq, lora_alpha).reshape(t, heads, nope + rope)
    kv = (c @ p["kv_b_proj"]["kernel"]).reshape(t, heads, nope + v_dim)
    k_r = _rotary(kv_a[:, None, r:], positions, theta, rope)
    q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], positions, theta, rope)], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r, (t, heads, rope))], axis=-1)
    v = kv[..., nope:]
    selects = kind.window is None  # (a sliding layer has no indexer, whatever the fault)
    if selects:
        q_i, k_i, w = index_scores(p["indexer"], cq, u, positions, heads=index_heads, dim=index_dim,
                                   rope=rope, theta=kind.theta, fault=fault)
    k_sel = topk // 2 if fault == "half_topk" else topk

    q_block = min(QUERY_BLOCK, t)
    n_blocks = -(-t // q_block)
    pad = n_blocks * q_block - t
    padded = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    q_p = padded(q)
    if selects:
        q_i, w = padded(q_i), padded(w)
    ki = jnp.arange(t)[None, :]

    def block(i):
        rows = lambda a: jax.lax.dynamic_slice_in_dim(a, i * q_block, q_block, axis=0)
        qi = (i * q_block + jnp.arange(q_block))[:, None]
        visible = (ki <= qi) & (mask[None, :] > 0)
        if not selects:
            chosen = visible if window is None else visible & (qi - ki < window)
        elif fault == "dense_attention":
            chosen = visible
        else:
            dots = jnp.einsum("qhd,kd->hqk", rows(q_i), k_i)
            if fault != "no_index_relu":
                dots = jax.nn.relu(dots)
            chosen = top_k_mask(jnp.einsum("hqk,qh->qk", dots, rows(w)), visible, k_sel)
        scores = jnp.einsum("qhd,khd->hqk", rows(q_p), k) / math.sqrt(nope + rope)
        if fault == SOFTMAX_PRECISION_CONTROL:
            scores = scores.astype(jnp.bfloat16).astype(F32)
        probs = jax.nn.softmax(jnp.where(chosen[None], scores, -1e30), axis=-1)
        if fault == SOFTMAX_PRECISION_CONTROL:
            probs = probs.astype(jnp.bfloat16).astype(F32)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(block, jnp.arange(n_blocks)).reshape(n_blocks * q_block, heads, v_dim)[:t]
    if gated and fault != "no_gate":
        out = out * jax.nn.sigmoid(u @ p["head_gate"]["kernel"])[:, :, None]
    return _proj(p["o_proj"], out.reshape(t, heads * v_dim), lora_alpha)


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
    return g * scaling


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
    "kind", "hidden", "eps", "topk", "index_heads", "index_dim", "index_theta", "gated", "rescale", "top_k",
    "scaling", "first", "lora_alpha", "fault"))
def _layer(layer, x, mask, positions, *, kind, hidden, eps, topk, index_heads, index_dim, index_theta, gated, rescale,
           top_k, scaling, first, lora_alpha=16.0, fault=None):
    """One row ``x [T, hidden]`` through one layer of ``kind``."""
    with jax.default_matmul_precision("highest"):
        p = _up(layer, fault)
        a = x + attention(
            p["attn"], _rms_norm(x, p["ln_attn"]["scale"], eps), mask, positions, kind,
            hidden=hidden, eps=eps, topk=topk, index_heads=index_heads, index_dim=index_dim, index_theta=index_theta,
            gated=gated, rescale=rescale, lora_alpha=lora_alpha, fault=fault)
        n = _rms_norm(a, p["ln_mlp"]["scale"], eps)
        mlp = p["mlp"]
        if "router" not in mlp:  # the leading dense layer
            y = _swiglu(mlp, n)
        else:
            g = gates(n @ mlp["router"]["kernel"], mlp["router_bias"], top_k, scaling, fault)
            y = _routed(mlp, n, g, first)
            if fault != "no_shared_expert":
                y = y + _swiglu(mlp["shared_expert"], n)
        return a + y


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
    by_type = kinds(dims)
    types = list(dims["layer_types"])
    statics = dict(
        hidden=int(dims["hidden_size"]),
        eps=float(dims["rms_norm_eps"]),
        topk=int(dims["index_topk"]),
        index_heads=int(dims["index_n_heads"]),
        index_dim=int(dims["index_head_dim"]),
        index_theta=float(dims["rope_theta"]),
        gated=dims.get("attention_gate_type") == "headwise",
        rescale=bool(dims.get("apply_mla_qkv_lora_rescale")),
        top_k=int(dims["num_experts_per_tok"]),
        scaling=float(dims["routed_scaling_factor"]),
        first=int(dims.get("moe_first_expert_held", 0)),
        lora_alpha=float(dims.get("lora_alpha", 16.0)),
        fault=fault,
    )
    rows = []
    for b in range(mask.shape[0]):
        x = embedding[jnp.asarray(input_ids)[b]]
        for l in range(int(dims["num_hidden_layers"])):
            layer = params[f"h_{l}"]
            if (types[l] == "full_attention") != ("indexer" in layer["attn"]):
                raise ValueError(f"layer {l}: layer_types says {types[l]!r}, the tree "
                                 f"{'has' if 'indexer' in layer['attn'] else 'lacks'} an indexer")
            x = _layer(layer, x, mask[b], positions[b], kind=by_type[types[l]], **statics)
        rows.append(x)
    return jnp.stack(rows)


def logits(params, dims, input_ids, attention_mask, span, fault=None):
    """Float32 logits ``[B, span[1] - span[0], vocab]`` of the backbone tree
    ``params`` on ``input_ids`` [B, T] with ``attention_mask`` [B, T]."""
    x = hidden(params, dims, input_ids, attention_mask, fault)
    return _head(params["ln_f"], params["lm_head"], x[:, span[0] : span[1]],
                 eps=float(dims["rms_norm_eps"]), fault=fault)
