"""The lfm2_moe family's own counts of required work.

Two kinds of layer. ``chipbench/flops.py``'s generic walk would charge every
layer the causal score and value products of ``num_attention_heads`` heads;
here:

- a **conv** layer (``tcfg.layer_layout(i).mixer == "conv"``) has no score
  matrix at all. Beside its two projections (2-D ``kernel`` leaves, the
  generic walk's ``2ab`` a token) it REQUIRES, a token, the depthwise conv
  (``2 x conv_L_cache`` operations a channel, entered under ``conv_weight``, a
  path of the tree, so that the mask decides its weight gradient, as
  ``flops.py`` enters Mamba-2's) and the two gates (one multiply a channel
  each, no parameter: ``mix``, whose backward costs twice its forward).
- a **full_attention** layer attends over every causal key at GQA heads of
  ``head_dim``: ``flops.attention_mix``.

Everything else is the generic walk's: the dense layers' and the router's 2-D
leaves, the held experts' 3-D leaves at ``2ab x k x moe/held_frac``, the tied
head on the response positions.

Kernel costs: ``flash_fwd`` and ``flash_bwd`` are ``flops.py``'s phases on the
attention layers alone (the flash kernels run nowhere else in this family).
The conv layers' element-wise fusions have a device time
(``short_conv_pass_device_ms``, ``short_conv_step_device_ms``) and NO share of
a roofline: on the chip they run in 76.8 and 21.4 ms a cycle where their
bytes at HBM's bandwidth would take 89.0 and 31.4 (my chip run, PR 60), because
the compiler keeps their operands in the chip's on-chip memory (``S(1)`` in
the events' layouts): an HBM byte floor does not bound them, and a share above
100 is a wrong count, not a fast kernel.
"""

from typing import Any, Dict, List

from chipbench import flops

CONV_UNDER = ("attn", "conv_weight")


def is_conv(tcfg, i: int) -> bool:
    return tcfg.layer_layout(i).mixer == "conv"


def conv_flops(tcfg, t: int) -> float:
    """One row of ``t`` tokens through one conv layer's depthwise conv, forward."""
    return 2.0 * int(tcfg.conv_L_cache) * int(tcfg.hidden_size) * t


def gate_flops(tcfg, t: int) -> float:
    """... and through its two gates: ``B * z`` and ``C * c``."""
    return 2.0 * int(tcfg.hidden_size) * t


def layer_forward(tcfg, i: int, layer_tree, t: int, stats: Dict[str, float]) -> Dict[str, Any]:
    """One row of ``t`` real tokens through layer ``i``, forward."""
    cost = flops.generic_layer_forward(tcfg, i, layer_tree, t, stats)
    if not is_conv(tcfg, i):
        return cost
    matmuls = dict(cost["matmuls"])
    matmuls[CONV_UNDER] = conv_flops(tcfg, t)
    return {"matmuls": matmuls, "mix": gate_flops(tcfg, t)}


def _attention(model, layers) -> List[int]:
    return [i for i in layers if not is_conv(model.tcfg, i)]


def flash_fwd(model, cycle: Dict[str, Any]) -> List[Dict[str, Any]]:
    """``flops.flash_fwd`` on the attention layers alone."""
    out = []
    for name, layers, lengths, times, _ in flops._passes(model, cycle):
        layers = _attention(model, layers)
        ops = sum(times * flops.attention_mix(model.tcfg, i, t) for i in layers for t in lengths)
        nbytes = sum(times * flops._flash_bytes(model, t, False) for _ in layers for t in lengths)
        if layers:
            out.append({"phase": name, "flops": ops, "bytes": nbytes})
    return out


def flash_bwd(model, cycle: Dict[str, Any]) -> List[Dict[str, Any]]:
    """``flops.flash_bwd`` likewise: four products for the forward's two."""
    layers = _attention(model, [i for i in range(model.n_layers) if i >= model.lowest_trained])
    lengths = [q + r for q, r in cycle["row_lengths"]]
    ops = sum(flops.MIX_BACKWARD * flops.attention_mix(model.tcfg, i, t) for i in layers for t in lengths)
    nbytes = sum(flops._flash_bytes(model, t, True) for _ in layers for t in lengths)
    if not layers:
        return []
    return [{"phase": "train_backward", "flops": model.epochs * ops, "bytes": model.epochs * nbytes}]
