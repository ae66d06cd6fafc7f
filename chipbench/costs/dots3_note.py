"""The dots3_note family's own counts of required work.

The stack has TWO kinds of attention layer (``TransformerConfig.
attention_sizes``), and the generic walk of ``chipbench/flops.py`` reads one
set of head sizes off the config: it would count a window layer's 64 heads
of 256 / 128 as the full layers' 128 of 192 / 128. ``layer_forward`` below
counts a KIND of layer:

- a window layer (``sliding_attention``): score and value products on the
  pairs inside the window, ``sum_t min(t + 1, 513)``, at ITS heads and sizes;
- a full layer: the products on the CHOSEN pairs, ``sum_t min(t + 1,
  index_topk)``, and the index scores of every causal pair, entered as
  ``chipbench/costs/glm_moe_dsa.py`` enters them (the indexer has no backward
  pass: ``2X`` under its own paths in ``matmuls`` and ``-X`` in ``mix``).

The projections (the headwise gate's ``head_gate`` and the indexer's three
included), the experts and the shared expert are the generic walk's: it reads
each layer's own tree, so the two kinds' unlike matrices are counted as they
are. ``flash_fwd`` and ``flash_bwd`` are ``flops.py``'s phases a kind of
layer. The kernels visit more than is required (every tile up to the diagonal
under a selection; whole 512-wide tiles at the window's edge), which lowers
their share.

``latent_ring_step`` is the window layers' absorbed attention in the decode
loop: a step reads the ring's latents and roped keys once (bytes bind: 1088
numbers a slot against ``2 x 64 x (1088 + 1024)`` operations).
``select_step`` is the full layers' index pass and absorbed attention over the
chosen slots in the same loop (bytes bind there too: 1152 a chosen slot and
256 a slot of index keys). ``window_latent_pass`` is the flash kernels' work
on the window layers alone, forward and backward, as one list of phases.
"""

from typing import Any, Dict, List

from chipbench import flops
from chipbench.costs import glm_moe_dsa

INDEXER = glm_moe_dsa.INDEXER
SCORES_UNDER = glm_moe_dsa.SCORES_UNDER
chosen_pairs = glm_moe_dsa.chosen_pairs
index_score_flops = glm_moe_dsa.index_score_flops


def layer_pairs(tcfg, i: int, t: int) -> float:
    """(query, key) pairs layer ``i`` keeps of a row of ``t`` tokens: inside
    its window, or its selection's ``min(t + 1, index_topk)``."""
    window = tcfg.layer_layout(i).window
    return flops.pairs(t, window) if window else chosen_pairs(t, int(tcfg.index_topk))


def layer_mix(tcfg, i: int, t: int) -> float:
    """Score and value products of layer ``i`` at its own heads and sizes."""
    s = tcfg.attention_sizes(i)
    return 2.0 * s.heads * (s.nope + s.rope + s.v) * layer_pairs(tcfg, i, t)


def layer_forward(tcfg, i: int, layer_tree, t: int, stats: Dict[str, float]) -> Dict[str, Any]:
    cost = flops.generic_layer_forward(tcfg, i, layer_tree, t, stats)
    matmuls, mix = dict(cost["matmuls"]), layer_mix(tcfg, i, t)
    if tcfg.layer_layout(i).indexer == "full":
        scores = index_score_flops(tcfg, flops.pairs(t, None))
        projections = [p for p in matmuls if p[:2] == INDEXER]
        forward_only = scores + sum(matmuls[p] for p in projections)
        for p in projections:
            matmuls[p] *= 2.0
        matmuls[SCORES_UNDER] += 2.0 * scores
        mix -= forward_only
    return {"matmuls": matmuls, "mix": mix}


def _flash_bytes(model, i: int, t: int, backward: bool) -> float:
    """One row through layer ``i``'s flash call: q, k, v read and o written
    once (backward: those and do read, dq, dk, dv written); under a selection
    its byte a (query, key) pair of the row too."""
    s = model.tcfg.attention_sizes(i)
    d_qk = s.nope + s.rope
    numbers = s.heads * (2 * d_qk + 2 * s.v) * (2 if backward else 1)
    selection = 0.0 if s.window or t <= int(model.tcfg.index_topk) else float(t * t)
    return float(model.act_bytes * t * numbers) + selection


def _forward_phases(model, cycle, keep) -> List[Dict[str, Any]]:
    out = []
    for name, layers, lengths, times, _ in flops._passes(model, cycle):
        mine = [i for i in layers if keep(i)]
        ops = sum(times * layer_mix(model.tcfg, i, t) for i in mine for t in lengths)
        nbytes = sum(times * _flash_bytes(model, i, t, False) for i in mine for t in lengths)
        out.append({"phase": name, "flops": ops, "bytes": nbytes})
    return out


def _backward_phases(model, cycle, keep) -> List[Dict[str, Any]]:
    mine = [i for i in range(model.n_layers) if i >= model.lowest_trained and keep(i)]
    lengths = [q + r for q, r in cycle["row_lengths"]]
    ops = sum(flops.MIX_BACKWARD * layer_mix(model.tcfg, i, t) for i in mine for t in lengths)
    nbytes = sum(_flash_bytes(model, i, t, True) for i in mine for t in lengths)
    return [{"phase": "train_backward", "flops": model.epochs * ops, "bytes": model.epochs * nbytes}]


def flash_fwd(model, cycle: Dict[str, Any]) -> List[Dict[str, Any]]:
    return _forward_phases(model, cycle, lambda i: True)


def flash_bwd(model, cycle: Dict[str, Any]) -> List[Dict[str, Any]]:
    return _backward_phases(model, cycle, lambda i: True)


def _windowed(model):
    return lambda i: model.tcfg.layer_layout(i).window is not None


def window_latent_pass(model, cycle: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The flash kernels' required work on the WINDOW layers, forward (prefill,
    scoring and its reference branch, the steps' forward) and backward."""
    keep = _windowed(model)
    if not any(keep(i) for i in range(model.n_layers)):
        return []
    backward = _backward_phases(model, cycle, keep)
    return _forward_phases(model, cycle, keep) + [p for p in backward if p["flops"] > 0.0]


def _absorbed_row_step(z, kept: int, act_bytes: int) -> Dict[str, float]:
    """One row, one layer of sizes ``z``, one decode step over ``kept`` slots
    in absorbed form: scores over the latent and the one roped key, ``sum p
    c``, the two folds through ``kv_b_proj``, each latent and roped key read
    once. ``kv_b_proj``'s own matrix is left out of the bytes, so a share
    reads low and never high."""
    r, dr = z.kv_lora_rank, z.rope
    return {"flops": 2.0 * z.heads * ((r + dr) + r) * kept + 2.0 * z.heads * r * (z.nope + z.v),
            "bytes": float(act_bytes * (r + dr) * kept)}


def ring_row_step(tcfg, i: int, s: int, act_bytes: int) -> Dict[str, float]:
    """Window layer ``i``'s step that sees ``s`` slots: the absorbed attention
    over the ``min(s, window)`` latents of the ring."""
    z = tcfg.attention_sizes(i)
    return _absorbed_row_step(z, min(s, int(z.window)), act_bytes)


def select_row_step(tcfg, i: int, s: int, act_bytes: int) -> Dict[str, float]:
    """Full layer ``i``'s step that sees ``s`` slots: the index pass over every
    slot (``2 HI DI s`` operations over ``DI s`` index keys) and the absorbed
    attention over the ``min(s, index_topk)`` chosen ones, as
    ``chipbench/costs/glm_moe_dsa.py::sparse_decode_row_step`` counts a `full`
    layer, at THIS layer's sizes. The top-k itself is left out."""
    step = _absorbed_row_step(tcfg.attention_sizes(i), min(s, int(tcfg.index_topk)), act_bytes)
    return {"flops": step["flops"] + index_score_flops(tcfg, s),
            "bytes": step["bytes"] + float(act_bytes * int(tcfg.index_head_dim) * s)}


def _decode_phase(model, cycle: Dict[str, Any], layers: List[int], row_step) -> List[Dict[str, Any]]:
    """``row_step`` summed over the decode loop, one phase: a row of ``q``
    prompt tokens and ``n`` new ones takes ``n - 1`` required steps (the
    prefill gives the first token), step ``j`` seeing its ``q + j + 1`` real
    slots, in each of ``layers``."""
    total = {"flops": 0.0, "bytes": 0.0}
    memo: Dict[Any, Any] = {}
    for q, n in cycle["row_lengths"]:
        if (q, n) not in memo:
            row = {"flops": 0.0, "bytes": 0.0}
            for i in layers:
                for j in range(max(n - 1, 0)):
                    step = row_step(model.tcfg, i, q + j + 1, model.act_bytes)
                    row["flops"] += step["flops"]
                    row["bytes"] += step["bytes"]
            memo[q, n] = row
        for key in total:
            total[key] += memo[q, n][key]
    return [{"phase": "decode", **total}] if total["flops"] > 0.0 else []


def latent_ring_step(model, cycle: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The decode loop's attention of the window layers."""
    return _decode_phase(model, cycle, [i for i in range(model.n_layers) if _windowed(model)(i)], ring_row_step)


def select_step(model, cycle: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The decode loop's index pass and attention under the selection, of the
    layers that select (the full ones)."""
    layers = [i for i in range(model.n_layers) if model.tcfg.layer_layout(i).indexer == "full"]
    return _decode_phase(model, cycle, layers, select_row_step)
