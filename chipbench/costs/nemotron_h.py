"""The nemotron_h family's own counts of required work.

Every layer is ONE sublayer (``tcfg.layer_layout(i)``: exactly one of
``mixer`` and ``ffn`` is ``"none"``), so ``chipbench/flops.py``'s generic
walk, which charges every layer the causal score and value products of
``num_attention_heads`` heads and reads Mamba-2 only under a model-wide
``tcfg.mixer``, is right about the leaves and wrong about the rest. Here, a
row of ``t`` real tokens through layer ``i``, forward:

- every 2-D ``kernel`` leaf the layer HAS ``2ab`` a token (``in_proj`` and
  ``out_proj`` of an ``M`` layer; q, k, v, o of the ``*`` layer; the router
  and the shared expert's two matrices of an ``E`` layer) and the two 3-D
  expert leaves ``2ab x 6 x moe/held_frac``: the generic walk's, and nothing
  for a sublayer the layer does not have;
- an ``M`` layer: the chunked scan (``ssm_costs.scan_costs``, no parameter:
  ``mix``, whose backward costs twice its forward) and the depthwise conv
  (``ssm_costs.conv_costs``, entered under ``conv_weight``, a path of the
  tree, so that the mask decides its weight gradient, as ``flops.py`` enters
  Falcon-H1's);
- the ``*`` layer: the causal score and value products at GQA 32/2 heads of
  128 (``flops.attention_mix``); an ``E`` layer: no ``mix`` at all.

Kernel costs: ``flash_fwd`` and ``flash_bwd`` are ``flops.py``'s phases on the
attention layers alone (the flash kernels run nowhere else in this family);
``moe_gmm`` is ``flops.py``'s own, which walks the 3-D leaves a layer has and
finds none on an ``M`` or ``*`` layer. Two of this family's own:

- ``mamba_step``: the decode loop's one-token update of every ``M`` layer's
  state (``ops/ssd.py::ssd_step``), ``ssm_costs.step_costs`` a row a layer a
  required step: the float32 state ``[64, 64, 128]`` read once and written
  once (2 x 2 MiB a row) against 3.1 MFLOP, so the bytes bind: at 128 rows a
  layer's state is 268 MB, which no on-chip memory holds;
- ``mamba_scan``: the chunked scan wherever whole rows go through an ``M``
  layer (prefill, scoring and its reference branch, the steps' forward, and
  the steps' backward at twice the forward's operations).
"""

from typing import Any, Dict, List

from chipbench import flops, ssm_costs

CONV_UNDER = ("mixer", "conv_weight")


def kind(tcfg, i: int) -> str:
    """``M``, ``*`` or ``E``: layer ``i``'s letter, from its layout."""
    layout = tcfg.layer_layout(i)
    if layout.mixer == "none":
        return "E"
    return "M" if layout.mixer == "mamba2" else "*"


def _mamba_shape(tcfg):
    return int(tcfg.mamba_heads), int(tcfg.mamba_head_dim), int(tcfg.mamba_state), int(tcfg.mamba_groups)


def scan_flops(tcfg, t: int) -> float:
    """One row of ``t`` tokens through one ``M`` layer's chunked scan, forward."""
    return ssm_costs.scan_costs(1, t, *_mamba_shape(tcfg), chunk=int(tcfg.mamba_chunk))["flops"]


def layer_forward(tcfg, i: int, layer_tree, t: int, stats: Dict[str, float]) -> Dict[str, Any]:
    """One row of ``t`` real tokens through layer ``i``, forward."""
    cost = flops.generic_layer_forward(tcfg, i, layer_tree, t, stats)  # the leaves; its `mix` is an attention layer's
    letter = kind(tcfg, i)
    if letter == "*":
        return cost
    matmuls = dict(cost["matmuls"])
    if letter == "E":
        return {"matmuls": matmuls, "mix": 0.0}
    matmuls[CONV_UNDER] = ssm_costs.conv_costs(1, t, int(tcfg.mamba_conv_channels), int(tcfg.mamba_conv))["flops"]
    return {"matmuls": matmuls, "mix": scan_flops(tcfg, t)}


def _of_kind(model, layers, letter: str) -> List[int]:
    return [i for i in layers if kind(model.tcfg, i) == letter]


def flash_fwd(model, cycle: Dict[str, Any]) -> List[Dict[str, Any]]:
    """``flops.flash_fwd`` on the attention layers alone."""
    out = []
    for name, layers, lengths, times, _ in flops._passes(model, cycle):
        layers = _of_kind(model, layers, "*")
        ops = sum(times * flops.attention_mix(model.tcfg, i, t) for i in layers for t in lengths)
        nbytes = sum(times * flops._flash_bytes(model, t, False) for _ in layers for t in lengths)
        if layers:
            out.append({"phase": name, "flops": ops, "bytes": nbytes})
    return out


def flash_bwd(model, cycle: Dict[str, Any]) -> List[Dict[str, Any]]:
    """``flops.flash_bwd`` likewise: four products for the forward's two."""
    layers = _of_kind(model, [i for i in range(model.n_layers) if i >= model.lowest_trained], "*")
    lengths = [q + r for q, r in cycle["row_lengths"]]
    ops = sum(flops.MIX_BACKWARD * flops.attention_mix(model.tcfg, i, t) for i in layers for t in lengths)
    nbytes = sum(flops._flash_bytes(model, t, True) for _ in layers for t in lengths)
    if not layers:
        return []
    return [{"phase": "train_backward", "flops": model.epochs * ops, "bytes": model.epochs * nbytes}]


def mamba_step(model, cycle: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The decode loop's one-token update of every ``M`` layer's state, one
    phase: a row of ``n`` new tokens takes ``n - 1`` required steps (the
    prefill gives the first token), each a ``ssm_costs.step_costs`` of one
    row in every ``M`` layer."""
    n_mamba = len(_of_kind(model, range(model.n_layers), "M"))
    steps = sum(max(n - 1, 0) for _, n in cycle["row_lengths"])
    if not n_mamba or not steps:
        return []
    step = ssm_costs.step_costs(1, *_mamba_shape(model.tcfg), act_bytes=model.act_bytes)
    return [{"phase": "decode", "flops": n_mamba * steps * step["flops"], "bytes": n_mamba * steps * step["bytes"]}]


def mamba_scan(model, cycle: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The chunked scan wherever whole rows go through an ``M`` layer, and the
    steps' backward through every ``M`` layer at or above the lowest trained
    leaf (twice the forward's operations; its bytes the forward's, read again,
    and as much written for the gradients). The pass the backward runs again
    (``jax.checkpoint``) is the forward's, not required twice."""
    tcfg, out = model.tcfg, []
    shape, chunk = _mamba_shape(tcfg), int(tcfg.mamba_chunk)

    def row(t):
        return ssm_costs.scan_costs(1, t, *shape, chunk=chunk, act_bytes=model.act_bytes)

    for name, layers, lengths, times, _ in flops._passes(model, cycle):
        n = len(_of_kind(model, layers, "M"))
        if n:
            out.append({"phase": name, "flops": n * sum(times * row(t)["flops"] for t in lengths),
                        "bytes": n * sum(times * row(t)["bytes"] for t in lengths)})
    n = len(_of_kind(model, [i for i in range(model.n_layers) if i >= model.lowest_trained], "M"))
    lengths = [q + r for q, r in cycle["row_lengths"]]
    if n:
        out.append({"phase": "train_backward",
                    "flops": model.epochs * n * sum(flops.MIX_BACKWARD * row(t)["flops"] for t in lengths),
                    "bytes": model.epochs * n * sum(2.0 * row(t)["bytes"] for t in lengths)})
    return out
