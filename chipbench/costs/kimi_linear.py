"""The kimi_linear family's own counts of required work.

Two kinds of layer. ``chipbench/flops.py``'s generic walk would charge every
layer the causal score and value products of ``num_attention_heads`` heads;
here:

- a **KDA** layer (``tcfg.layer_layout(i).mixer == "kda"``) has no score
  matrix at all. The recurrence REQUIRES, a head a token, three products of
  ``d x d`` multiply-adds (``d = kda_head_dim``): what the decayed state
  answers for the key (``S'^T k``), the rank-one write (``k u^T``) and the
  read-out (``S^T q``): ``6 d^2`` operations. Nothing of the chunked form's
  solve or its matrices inside a chunk (``ops/delta_rule.py``), which are the
  implementation's, and nothing of the pass the backward runs again. The
  three depthwise convs (``2 x taps`` a channel a token) are entered under
  ``conv_weight``, a path of the tree, as ``flops.py`` enters Mamba-2's.
- a **latent** layer attends over every causal key at q/k ``dims_per_head``
  (192) and v ``v_head_dim`` (128): ``flops.attention_mix``.

Everything else is the generic walk's: 2-D ``kernel``, ``lora_a``, ``lora_b``
leaves at ``2ab`` a token (the gates' low-rank pairs and ``b_proj`` among
them), the held experts' 3-D leaves at ``2ab x k x moe/held_frac``.

Kernel costs: ``flash_fwd`` and ``flash_bwd`` are ``flops.py``'s phases on the
latent layers alone (the flash kernels run nowhere else in this family);
``kda_scan`` is the recurrence of the long passes, forward and backward,
against its operations and the bytes of ``q, k, v, g, beta`` read and ``o``
written once; ``kda_step`` the decode loop's one-token update of the KDA
layers' states: the state read and written once a step a row a layer, which
binds (2 x 2 MiB against 3.1 MFLOP).
"""

from typing import Any, Dict, List

from chipbench import flops

CONV_UNDER = ("attn", "conv_weight")


def is_kda(tcfg, i: int) -> bool:
    return tcfg.layer_layout(i).mixer == "kda"


def scan_flops(tcfg, t: int) -> float:
    """One row of ``t`` tokens through one KDA layer's recurrence, forward."""
    return 6.0 * int(tcfg.kda_heads) * int(tcfg.kda_head_dim) ** 2 * t


def layer_forward(tcfg, i: int, layer_tree, t: int, stats: Dict[str, float]) -> Dict[str, Any]:
    """One row of ``t`` real tokens through layer ``i``, forward."""
    cost = flops.generic_layer_forward(tcfg, i, layer_tree, t, stats)
    if not is_kda(tcfg, i):
        return cost
    matmuls = dict(cost["matmuls"])
    matmuls[CONV_UNDER] = 2.0 * int(tcfg.kda_conv) * 3 * int(tcfg.kda_heads) * int(tcfg.kda_head_dim) * t
    return {"matmuls": matmuls, "mix": scan_flops(tcfg, t)}


def _latent(model, layers) -> List[int]:
    return [i for i in layers if not is_kda(model.tcfg, i)]


def flash_fwd(model, cycle: Dict[str, Any]) -> List[Dict[str, Any]]:
    """``flops.flash_fwd`` on the latent layers alone."""
    out = []
    for name, layers, lengths, times, _ in flops._passes(model, cycle):
        layers = _latent(model, layers)
        ops = sum(times * flops.attention_mix(model.tcfg, i, t) for i in layers for t in lengths)
        nbytes = sum(times * flops._flash_bytes(model, t, False) for _ in layers for t in lengths)
        if layers:
            out.append({"phase": name, "flops": ops, "bytes": nbytes})
    return out


def flash_bwd(model, cycle: Dict[str, Any]) -> List[Dict[str, Any]]:
    """``flops.flash_bwd`` likewise: four products for the forward's two."""
    layers = _latent(model, [i for i in range(model.n_layers) if i >= model.lowest_trained])
    lengths = [q + r for q, r in cycle["row_lengths"]]
    ops = sum(flops.MIX_BACKWARD * flops.attention_mix(model.tcfg, i, t) for i in layers for t in lengths)
    nbytes = sum(flops._flash_bytes(model, t, True) for _ in layers for t in lengths)
    if not layers:
        return []
    return [{"phase": "train_backward", "flops": model.epochs * ops, "bytes": model.epochs * nbytes}]


def _scan_bytes(model, t: int, backward: bool) -> float:
    """One row, one layer: q, k, v read and o written in the activations'
    dtype, the log decays (a channel) and beta (a head) read in float32; the
    backward reads those and do and writes dq, dk, dv, dg, dbeta."""
    tcfg = model.tcfg
    heads, width = int(tcfg.kda_heads), int(tcfg.kda_heads) * int(tcfg.kda_head_dim)
    fwd = model.act_bytes * 4 * width + 4 * (width + heads)
    if not backward:
        return float(t * fwd)
    return float(t * (fwd + model.act_bytes * 4 * width + 4 * (width + heads)))


def kda_scan(model, cycle: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The recurrence wherever whole rows go through a KDA layer (prefill,
    scoring with its reference branch, the steps' forward), and the steps'
    backward through every KDA layer at or above the lowest trained leaf
    (twice the forward's operations). The pass the backward runs again
    (``jax.checkpoint``) is the forward's, not required twice."""
    tcfg, out = model.tcfg, []
    for name, layers, lengths, times, _ in flops._passes(model, cycle):
        n = sum(is_kda(tcfg, i) for i in layers)
        if n:
            out.append({"phase": name, "flops": n * sum(times * scan_flops(tcfg, t) for t in lengths),
                        "bytes": n * sum(times * _scan_bytes(model, t, False) for t in lengths)})
    n = sum(is_kda(tcfg, i) for i in range(model.n_layers) if i >= model.lowest_trained)
    lengths = [q + r for q, r in cycle["row_lengths"]]
    if n:
        out.append({"phase": "train_backward",
                    "flops": model.epochs * n * sum(flops.MIX_BACKWARD * scan_flops(tcfg, t) for t in lengths),
                    "bytes": model.epochs * n * sum(_scan_bytes(model, t, True) for t in lengths)})
    return out


def kda_step(model, cycle: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The decode loop's one-token update of every KDA layer's state, one
    phase: a row of ``n`` new tokens takes ``n - 1`` required steps (the
    prefill gives the first token), each reading and writing the row's float32
    state ``[heads, d, d]`` of every KDA layer once."""
    tcfg = model.tcfg
    n_kda = sum(is_kda(tcfg, i) for i in range(model.n_layers))
    steps = sum(max(n - 1, 0) for _, n in cycle["row_lengths"])
    if not n_kda or not steps:
        return []
    state = 4.0 * int(tcfg.kda_heads) * int(tcfg.kda_head_dim) ** 2
    return [{"phase": "decode", "flops": n_kda * steps * scan_flops(tcfg, 1), "bytes": n_kda * steps * 2.0 * state}]
