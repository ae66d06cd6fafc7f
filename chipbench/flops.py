"""Operations and bytes the algorithm requires, counted from the model as it is.

Required means: what the passes need on the REAL tokens of their rows,
whatever implements them. Nothing recomputed (a rematerialised scan, a flash
backward that builds the scores again: one forward), nothing padded (slots
of a row beyond its real tokens), nothing a tile visits outside the causal
or window count. XLA's cost analysis of the compiled program (the program's
own ``throughput/mfu``) counts padded and recomputed work too, which is why
the yardstick does not use it.

**Forward, one row of ``t = q + r`` real tokens.** EVERY layer
``backbone["h_<i>"]`` is walked, none stands for the others:

- a 2-D leaf ``kernel``, ``lora_a`` or ``lora_b`` of shape ``[a, b]``:
  ``2ab`` a real token;
- a 3-D expert leaf ``[E_held, a, b]``: ``2ab * k * f`` a real token, ``k``
  the experts a token asks for (``tcfg.num_experts_per_tok``), ``f`` the
  share of real assignments that fell on an expert held here
  (``moe/held_frac`` of the cycle's own step records; 1 where every expert
  is held);
- attention's score and value products, from ``trainer.tcfg`` and never from
  a leaf's name: ``2 * heads * (D_qk + D_v) * pairs_i(t)``, ``pairs_i(t) =
  sum_j min(j + 1, window_i)``: the causal count where no window binds;
- a Mamba-2 mixer's scan and conv (``ssm_costs.py``).

The head runs on the ``r`` response positions (the program's
``logits_span``), and so does the value head where the tree has one.

**Backward, from ``trainer.param_mask``.** A weight gradient (``2ab`` again)
for each matmul whose leaf trains. An activation-gradient pass (the matmuls
once more; score and value products and the scan twice, their backward
being four products for the forward's two) for every layer at or above the
lowest trained leaf of the graph ``wte -> h_0 .. h_{L-1} -> ln_f ->
lm_head | v_head``. ``wte`` trains under PPO today, so every block pays the
activation gradients although only the unfrozen ones pay weight gradients;
under LoRA only the adapted blocks do. (A lowest layer whose first trained
leaf sits behind its input projections is counted whole: high by those
projections' activation gradient, in that one layer. No cell has one.)

**A family's own counts.** ``chipbench/costs/<family>.py``, found by
``importlib`` as ``chipbench/reference/<family>.py`` is, may define
``layer_forward(tcfg, i, layer_tree, t, stats)`` and any of the kernel
costs below under the same names; absent, the generic walk runs. That is
how a later PR counts a block this file has never seen without editing it.

**Kernel costs** (``layers.py::trace_op_roofline`` names one in a metric's
``costs``): ``flash_fwd``, ``flash_bwd``, ``moe_gmm``. Each takes the model
and ONE cycle's records and returns a list of phases ``{"phase", "flops",
"bytes"}``: the REQUIRED work of the operations the metric's pattern
matches, over the whole cycle (prefill, decode, the scoring forward with its
reference branch, the optimizer steps). A phase's floor is the larger of its
two bounds; the cycle's floor is their sum. Where a count cannot be exact
(which held experts a decode step hit) it is the lower bound, so a share of
a roofline never reads high.
"""

import importlib
import math
import statistics
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from chipbench import ssm_costs

MATMUL_LEAVES = ("kernel", "lora_a", "lora_b")
# forward passes = 1; a backward pass through score/value products or a scan
# costs twice its forward (four products for two)
MIX_BACKWARD = 2.0


class Uncountable(Exception):
    """The tree is one the walk cannot read: the metric is left out, with
    this reason printed, and the run goes on."""


def pairs(t: int, window: Optional[int]) -> float:
    """(query, key) pairs a causal row of ``t`` tokens needs: ``sum_j min(j +
    1, window)``; ``t (t + 1) / 2`` where no window binds."""
    t = int(t)
    if not window or window >= t:
        return t * (t + 1) / 2.0
    w = int(window)
    return w * (w + 1) / 2.0 + (t - w) * float(w)


def family_module(family: Optional[str]):
    """``chipbench/costs/<family>.py`` if the family brought one."""
    if not family:
        return None
    name = f"chipbench.costs.{family}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name == name:
            return None
        raise


# ---------------------------------------------------------------------------
# the tree
# ---------------------------------------------------------------------------


def _by_path(tree) -> List[Tuple[Tuple[str, ...], Any]]:
    """``[(path of keys, leaf)]`` of a tree of dicts."""
    import jax

    return [(tuple(str(getattr(p, "key", getattr(p, "name", p))) for p in path), x)
            for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _leaves(tree) -> List[Tuple[Tuple[str, ...], Tuple[int, ...]]]:
    """``[(path of keys, shape)]`` of a parameter (or shape) tree."""
    return [(keys, tuple(int(d) for d in x.shape)) for keys, x in _by_path(tree)]


def _mask_of(mask_tree) -> Dict[Tuple[str, ...], bool]:
    return {keys: bool(np.all(np.asarray(m))) for keys, m in _by_path(mask_tree)}


def generic_layer_forward(tcfg, i: int, layer_tree, t: int, stats: Dict[str, float]
                          ) -> Dict[str, Any]:
    """One layer, one row of ``t`` real tokens, forward: ``{"matmuls": {leaf
    path: FLOPs}, "mix": FLOPs}``. ``matmuls`` are the products with a
    parameter (the mask decides which of them also pay a weight gradient);
    ``mix`` is the work with none: score and value products, the scan."""
    matmuls: Dict[Tuple[str, ...], float] = {}
    k = float(getattr(tcfg, "num_experts_per_tok", 0) or 0)
    f = float(stats.get("moe/held_frac", 1.0))
    leaves = _leaves(layer_tree)
    for path, shape in leaves:
        if len(shape) == 2 and path[-1] in MATMUL_LEAVES:
            matmuls[path] = 2.0 * shape[0] * shape[1] * t
        elif len(shape) == 3:  # [experts held, a, b]: k * f of them a token
            if k <= 0:
                raise Uncountable(f"h_{i}{list(path)}: a 3-D leaf {shape} in a model whose "
                                  "tcfg.num_experts_per_tok is 0")
            matmuls[path] = 2.0 * shape[1] * shape[2] * k * f * t
    mix = attention_mix(tcfg, i, t)
    if getattr(tcfg, "mixer", "none") == "mamba2":
        shape = (tcfg.mamba_heads, tcfg.mamba_head_dim, tcfg.mamba_state, tcfg.mamba_groups)
        mix += ssm_costs.scan_costs(1, t, *shape, chunk=tcfg.mamba_chunk)["flops"]
        conv = ssm_costs.conv_costs(1, t, tcfg.mamba_conv_channels, tcfg.mamba_conv)["flops"]
        conv_path = next((p for p, _ in leaves if p[-1] == "conv_weight"), None)
        if conv_path is None:
            mix += conv
        else:
            matmuls[conv_path] = conv
    elif getattr(tcfg, "mixer", "none") != "none":
        raise Uncountable(f"h_{i}: a mixer {tcfg.mixer!r} this walk has no count for "
                          "(add chipbench/costs/<family>.py)")
    return {"matmuls": matmuls, "mix": mix}


def attention_dims(tcfg) -> Tuple[int, int, int, int]:
    """heads, key/value heads, q/k head size, v head size: from the config."""
    d_qk = int(tcfg.dims_per_head)
    d_v = int(getattr(tcfg, "v_head_dim", None) or d_qk)
    return int(tcfg.num_heads), int(tcfg.kv_heads), d_qk, d_v


def attention_mix(tcfg, i: int, t: int) -> float:
    heads, _, d_qk, d_v = attention_dims(tcfg)
    return 2.0 * heads * (d_qk + d_v) * pairs(t, tcfg.layer_layout(i).window)


class Model:
    """What the counts need of a trainer: every layer's own tree, the mask,
    the heads and which layers the reference forward of scoring runs."""

    def __init__(self, trainer, family: Optional[str] = None):
        self.tcfg = trainer.tcfg
        self.family = family_module(family)
        self.layer_forward: Callable = getattr(self.family, "layer_forward", generic_layer_forward)
        self.n_layers = int(self.tcfg.num_layers)
        try:
            self._read(trainer)
        except (KeyError, TypeError, AttributeError, IndexError) as e:
            raise Uncountable(f"not a tree of {self.n_layers} h_<i> blocks with a head "
                              f"({type(e).__name__}: {e})") from e

    def _read(self, trainer) -> None:
        from chipbench.checks import backbone_of

        params, mask = trainer.state.params, trainer.param_mask
        bb, bb_mask = backbone_of(params), backbone_of(mask)
        self.layers = [bb[f"h_{i}"] for i in range(self.n_layers)]
        self.layer_masks = [_mask_of(bb_mask[f"h_{i}"]) for i in range(self.n_layers)]
        # the heads' matmuls: lm_head's kernel or the tied embedding, and the
        # value head's where the tree has one
        top = "lm_head" if "lm_head" in bb else "wte"
        self.head = {(top,) + p: s for p, s in _leaves(bb[top]) if len(s) == 2}
        self.head_mask = {(top,) + p: m for p, m in _mask_of(bb_mask[top]).items()}
        self.value_head: Dict[Tuple[str, ...], Tuple[int, ...]] = {}
        if "v_head" in params:
            self.value_head = {("v_head",) + p: s for p, s in _leaves(params["v_head"])
                               if len(s) == 2 and p[-1] in MATMUL_LEAVES}
            self.head_mask.update({("v_head",) + p: m for p, m in _mask_of(mask["v_head"]).items()})
        # the lowest trained leaf of the graph, by position: -1 in front of the
        # blocks, block i at i, the final norm at L, the heads at L + 1
        lowest = math.inf
        for name, sub in bb_mask.items():
            if not any(_mask_of(sub).values()):
                continue
            if name.startswith("h_") and name[2:].isdigit():
                lowest = min(lowest, int(name[2:]))
            elif name == "ln_f":
                lowest = min(lowest, self.n_layers)
            elif name == "lm_head":
                lowest = min(lowest, self.n_layers + 1)
            else:  # wte, wpe, an embedding norm: in front of every block
                lowest = min(lowest, -1)
        if any(self.head_mask.get(p, False) for p in self.value_head):
            lowest = min(lowest, self.n_layers + 1)
        self.lowest_trained = lowest
        # the reference forward of scoring: the blocks the snapshot holds
        ref = getattr(trainer, "ref_params", None)
        ref = backbone_of(ref) if isinstance(ref, dict) else {}
        self.ref_layers = sorted(int(k[2:]) for k in ref if k.startswith("h_") and k[2:].isdigit())
        self.epochs = int(getattr(trainer.config.method, "ppo_epochs", 1))
        self.act_bytes = int(np.dtype(self.tcfg.dtype).itemsize)
        self.param_bytes = int(np.dtype(self.tcfg.param_dtype).itemsize)

    def layer(self, i: int, t: int, stats: Dict[str, float]) -> Dict[str, Any]:
        try:
            cost = self.layer_forward(self.tcfg, i, self.layers[i], t, stats)
            cost["matmuls"], cost["mix"]
        except Uncountable:
            raise
        except (KeyError, TypeError, AttributeError, IndexError) as e:
            raise Uncountable(f"h_{i}: {self.layer_forward.__module__}.layer_forward gave no "
                              f"'matmuls' and 'mix' ({type(e).__name__}: {e})") from e
        return cost

    def trained(self, i: int, path: Tuple[str, ...]) -> bool:
        return self.layer_masks[i].get(tuple(path), False)


def cycle_stats(cycle: Dict[str, Any]) -> Dict[str, float]:
    """The step records' statistics a count reads: medians over the cycle."""
    out = {}
    for key in ("moe/held_frac",):
        vals = [float(s[key]) for s in cycle.get("steps", ()) if key in s]
        if vals:
            out[key] = statistics.median(vals)
    return out


# ---------------------------------------------------------------------------
# the optimizer steps (learn_mfu_pct)
# ---------------------------------------------------------------------------


def row_flops(model: Model, q: int, r: int, stats: Dict[str, float]) -> Dict[str, float]:
    """Forward and backward FLOPs one row of an optimizer step requires."""
    t = int(q) + int(r)
    forward = weight_grads = act_grads = 0.0
    for i in range(model.n_layers):
        cost = model.layer(i, t, stats)
        matmul = sum(cost["matmuls"].values())
        forward += matmul + cost["mix"]
        weight_grads += sum(v for p, v in cost["matmuls"].items() if model.trained(i, p))
        if i >= model.lowest_trained:
            act_grads += matmul + MIX_BACKWARD * cost["mix"]
    # the gradient goes on through a head only into something that trains
    # below it: the final norm or anything under it
    through_heads = model.lowest_trained <= model.n_layers
    for leaves in (model.head, model.value_head):
        for path, shape in leaves.items():
            flops = 2.0 * shape[0] * shape[1] * r
            forward += flops
            if model.head_mask.get(path, False):
                weight_grads += flops
            if through_heads:
                act_grads += flops
    return {"forward": forward, "weight_grads": weight_grads, "act_grads": act_grads,
            "total": forward + weight_grads + act_grads}


def learn_flops_of_cycle(trainer, cycle: Dict[str, Any], family: Optional[str] = None,
                         model: Optional[Model] = None) -> float:
    """Every delivered rollout of the cycle is learned from ``ppo_epochs`` times."""
    model = model or Model(trainer, family)
    stats = cycle_stats(cycle)
    memo: Dict[Tuple[int, int], float] = {}
    total = 0.0
    for q, r in cycle["row_lengths"]:
        if (q, r) not in memo:
            memo[(q, r)] = row_flops(model, q, r, stats)["total"]
        total += memo[(q, r)]
    return model.epochs * total


# ---------------------------------------------------------------------------
# kernel costs (trace_op_roofline)
# ---------------------------------------------------------------------------


def _phase(name: str, flops: float, nbytes: float) -> Dict[str, Any]:
    return {"phase": name, "flops": float(flops), "bytes": float(nbytes)}


def _passes(model: Model, cycle: Dict[str, Any]
            ) -> List[Tuple[str, Sequence[int], List[int], float, float]]:
    """The forward passes of one cycle that run whole rows through blocks:
    ``(phase, layers, row lengths, times each row runs, program runs)``.
    Decoding is not among them (one token a step: ``moe_gmm`` counts it
    itself, the flash kernels never see it)."""
    rows = cycle["row_lengths"]
    every = list(range(model.n_layers))
    whole = [q + r for q, r in rows]
    out = [("prefill", every, [q for q, _ in rows], 1.0, 1.0),
           ("score", every, whole, 1.0, 1.0)]
    if model.ref_layers:
        out.append(("score_reference", model.ref_layers, whole, 1.0, 1.0))
    steps = float(max(len(cycle.get("steps", ())), 1))
    out.append(("train_forward", every, whole, float(model.epochs), steps))
    return out


def _flash_bytes(model: Model, t: int, backward: bool) -> float:
    """One row, one layer: q, k, v read and o written once; backward reads
    those and do, and writes dq, dk, dv. K and V have the key/value heads."""
    heads, kv, d_qk, d_v = attention_dims(model.tcfg)
    fwd = heads * d_qk + kv * d_qk + kv * d_v + heads * d_v
    if not backward:
        return float(model.act_bytes * t * fwd)
    return float(model.act_bytes * t * (fwd + heads * d_v + heads * d_qk + kv * d_qk + kv * d_v))


def flash_fwd(model: Model, cycle: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The flash forward kernel runs wherever whole rows go through a block:
    prefill, scoring (policy and reference branch), the steps' forward."""
    out = []
    for name, layers, lengths, times, _ in _passes(model, cycle):
        flops = sum(times * attention_mix(model.tcfg, i, t) for i in layers for t in lengths)
        nbytes = sum(times * _flash_bytes(model, t, False) for _ in layers for t in lengths)
        out.append(_phase(name, flops, nbytes))
    return out


def flash_bwd(model: Model, cycle: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The optimizer steps' backward through every layer at or above the
    lowest trained leaf: four products for the forward's two. The scores a
    fused kernel builds again are the forward's, not required twice."""
    layers = [i for i in range(model.n_layers) if i >= model.lowest_trained]
    lengths = [q + r for q, r in cycle["row_lengths"]]
    flops = sum(MIX_BACKWARD * attention_mix(model.tcfg, i, t) for i in layers for t in lengths)
    nbytes = sum(_flash_bytes(model, t, True) for _ in layers for t in lengths)
    return [_phase("train_backward", model.epochs * flops, model.epochs * nbytes)]


def _expert_leaves(model: Model, i: int) -> List[Tuple[Tuple[str, ...], Tuple[int, ...]]]:
    return [(p, s) for p, s in _leaves(model.layers[i]) if len(s) == 3]


def moe_gmm(model: Model, cycle: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Every grouped expert matmul of the cycle. FLOPs: ``2ab`` an
    assignment that fell on a held expert (``k * f`` a real token). Bytes:
    the assignments' rows in and out, and the weights of the experts hit, at
    their lower bound: a token's ``k`` experts are distinct, so a call that
    sees any token hits ``ceil(k * f)`` held experts or more (the mean token's
    count; which ones a step hit is on no record). The backward pass is the
    forward's work once more for the activation gradients of a layer at or
    above the lowest trained leaf, and once more where the leaf trains."""
    k = float(getattr(model.tcfg, "num_experts_per_tok", 0) or 0)
    f = float(cycle_stats(cycle).get("moe/held_frac", 1.0))
    leaves = {i: _expert_leaves(model, i) for i in range(model.n_layers)}
    if k <= 0 or not any(leaves.values()):
        return []
    hit = math.ceil(k * f - 1e-9)

    def work(layers, tokens: float, calls: float, backward: bool = False):
        """``tokens`` real tokens through ``layers``; each leaf's matmul runs
        ``calls`` times and reads its weights once a call."""
        flops = nbytes = 0.0
        for i in layers:
            for path, (held, a, b) in leaves[i]:
                passes = 1.0
                if backward:
                    passes = float(i >= model.lowest_trained) + float(model.trained(i, path))
                flops += passes * 2.0 * a * b * k * f * tokens
                nbytes += passes * (model.act_bytes * (a + b) * k * f * tokens
                                    + calls * model.param_bytes * min(hit, held) * a * b)
        return flops, nbytes

    out = []
    every = range(model.n_layers)
    for name, layers, lengths, times, calls in _passes(model, cycle):
        flops, nbytes = work(layers, times * sum(lengths), calls)
        out.append(_phase(name, flops, nbytes))
        if name == "train_forward":
            flops, nbytes = work(layers, times * sum(lengths), calls, backward=True)
            out.append(_phase("train_backward", flops, nbytes))
    # decoding: a step a new token after the first, which the prefill gives
    new = [max(r - 1, 0) for _, r in cycle["row_lengths"]]
    flops, nbytes = work(every, float(sum(new)), float(max(new, default=0)))
    out.append(_phase("decode", flops, nbytes))
    return out


KERNEL_COSTS: Dict[str, Callable] = {"flash_fwd": flash_fwd, "flash_bwd": flash_bwd,
                                     "moe_gmm": moe_gmm}


def kernel_costs(name: str, model: Model) -> Callable:
    """The cost function a metric's ``costs`` names: the family's, else this file's."""
    fn = getattr(model.family, name, None) or KERNEL_COSTS.get(name)
    if not callable(fn):
        raise ValueError(f"no kernel cost function {name!r}: not in the family's "
                         f"chipbench/costs file, and this file has {sorted(KERNEL_COSTS)}")
    return fn


def floor_seconds(phases: List[Dict[str, Any]], peak_row: Dict[str, float], chips: int) -> float:
    """Sum over the phases of the larger of the two bounds."""
    return sum(max(p["flops"] / (peak_row["bf16_flops_per_s"] * chips),
                   p["bytes"] / (peak_row["hbm_bytes_per_s"] * chips)) for p in phases)
