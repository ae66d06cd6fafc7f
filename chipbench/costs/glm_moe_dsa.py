"""The glm_moe_dsa family's own counts of required work.

Under a learned selection a query's softmax runs over its ``index_topk``
chosen keys, so the algorithm REQUIRES ``sum_t min(t + 1, index_topk)`` score
and value pairs a row a layer, not the causal ``t (t + 1) / 2`` the generic
walk of ``chipbench/flops.py`` counts (it would read ``learn_mfu_pct`` high by
the pairs nobody needs), and on a layer that selects for itself (``full``)
the index scores of EVERY causal pair, which the walk does not know.
``layer_forward`` below counts both; the projections (the indexer's three
included), the experts and the shared expert are the generic walk's. What the
program computes beyond that (today: masked dense scores over whole groups of
keys, ``models/transformer.py::selected_attention``) is not required and
lowers the share.

The indexer has NO backward pass (``top_k`` has no gradient; the program puts
``stop_gradient`` on its inputs), and the hook's two buckets both have one:
``flops.py::row_flops`` charges a layer at or above the lowest trained leaf
``matmuls`` once more and ``mix`` twice more. So the indexer's forward work
``X`` (its three projections and its scores) is entered as ``2X`` under the
indexer's own paths in ``matmuls`` and ``-X`` in ``mix``: the forward counts
``X``, the activation-gradient pass ``2X - 2X = 0``. Its leaves carry no
adapter and never train, so no weight gradient is counted for them either.

``sparse_decode`` is the decode loop's attention: the index pass over every
slot on a ``full`` layer, and on every layer the absorbed attention over the
chosen slots only. ``flash_fwd`` and ``flash_bwd`` are ``flops.py``'s phases
with the chosen pairs in place of the causal ones: under a selection the
flash kernels run with one more mask a tile (``ops/flash_attention.py``,
``selection=``) and visit every tile up to the diagonal, which is not
required and lowers their share.
"""

from typing import Any, Dict, List

from chipbench import flops

INDEXER = ("attn", "indexer")
# the index scores have no leaf of their own: they are entered under the
# projection that makes their queries (a path of the tree, as every key of
# ``matmuls`` has to be)
SCORES_UNDER = INDEXER + ("wq_b", "kernel")


def chosen_pairs(t: int, topk: int) -> float:
    """(query, key) pairs a row of ``t`` tokens keeps: ``sum_j min(j + 1, topk)``."""
    return flops.pairs(t, topk)


def index_score_flops(tcfg, pairs: float) -> float:
    """``I = sum_j w_j relu(qI_j . kI)``: one dot of ``index_head_dim`` a head a pair."""
    return 2.0 * int(tcfg.index_heads) * int(tcfg.index_head_dim) * pairs


def layer_forward(tcfg, i: int, layer_tree, t: int, stats: Dict[str, float]) -> Dict[str, Any]:
    """One row of ``t`` real tokens through layer ``i``, forward: the generic
    walk's matmuls, score and value products on the CHOSEN pairs, and on a
    ``full`` layer the index scores of every causal pair; the indexer's work
    entered so that no backward pass is counted for it (above)."""
    cost = flops.generic_layer_forward(tcfg, i, layer_tree, t, stats)
    topk = int(getattr(tcfg, "index_topk", 0) or 0)
    if not topk:
        return cost
    heads, _, d_qk, d_v = flops.attention_dims(tcfg)
    mix = 2.0 * heads * (d_qk + d_v) * chosen_pairs(t, topk)
    matmuls = dict(cost["matmuls"])
    if tcfg.layer_layout(i).indexer == "full":
        scores = index_score_flops(tcfg, flops.pairs(t, None))
        projections = [p for p in matmuls if p[:2] == INDEXER]
        forward_only = scores + sum(matmuls[p] for p in projections)
        for p in projections:
            matmuls[p] *= 2.0
        matmuls[SCORES_UNDER] += 2.0 * scores
        mix -= forward_only
    return {"matmuls": matmuls, "mix": mix}


def _selection_bytes(t: int, topk: int) -> float:
    """The selection a pass of more than ``topk`` tokens reads: a byte a
    (query, key) pair of the row, once (the kernels read a query block's
    columns again for every head: not required)."""
    return float(t * t) if t > topk else 0.0


def flash_fwd(model, cycle: Dict[str, Any]) -> List[Dict[str, Any]]:
    """``flops.flash_fwd`` on the chosen pairs: wherever whole rows go through
    a block (prefill, scoring with its reference branch, the steps' forward)."""
    tcfg, topk = model.tcfg, int(model.tcfg.index_topk)
    heads, _, d_qk, d_v = flops.attention_dims(tcfg)
    out = []
    for name, layers, lengths, times, _ in flops._passes(model, cycle):
        per_layer = sum(times * 2.0 * heads * (d_qk + d_v) * chosen_pairs(t, topk) for t in lengths)
        nbytes = sum(times * (flops._flash_bytes(model, t, False) + _selection_bytes(t, topk)) for t in lengths)
        out.append({"phase": name, "flops": len(layers) * per_layer, "bytes": len(layers) * nbytes})
    return out


def flash_bwd(model, cycle: Dict[str, Any]) -> List[Dict[str, Any]]:
    """``flops.flash_bwd`` on the chosen pairs: four products for the
    forward's two, through every layer at or above the lowest trained leaf."""
    tcfg, topk = model.tcfg, int(model.tcfg.index_topk)
    heads, _, d_qk, d_v = flops.attention_dims(tcfg)
    layers = sum(1 for i in range(model.n_layers) if i >= model.lowest_trained)
    lengths = [q + r for q, r in cycle["row_lengths"]]
    ops = sum(flops.MIX_BACKWARD * 2.0 * heads * (d_qk + d_v) * chosen_pairs(t, topk) for t in lengths)
    nbytes = sum(flops._flash_bytes(model, t, True) + _selection_bytes(t, topk) for t in lengths)
    return [{"phase": "train_backward", "flops": model.epochs * layers * ops, "bytes": model.epochs * layers * nbytes}]


def sparse_decode_row_step(tcfg, s: int, full: bool, act_bytes: int) -> Dict[str, float]:
    """One row, one layer, one decode step that sees ``s`` slots. On a
    ``full`` layer the index pass: ``2 HI DI s`` operations over ``DI s``
    index keys. On every layer the attention over the ``min(s, index_topk)``
    chosen slots in absorbed form: scores over the latent and the one roped
    key (``2 H (r + dr)`` a slot), ``sum p c`` (``2 H r``), the two folds
    through ``kv_b_proj`` (``2 H r (dn + dv)``), and the chosen slots' latent
    and roped key read once. The same count whatever implements it; the
    top-k itself (comparisons, no FLOP of a matmul) and ``kv_b_proj``'s own
    matrix are left out, so the share reads low by them and never high."""
    H, r, dr = int(tcfg.num_heads), int(tcfg.kv_lora_rank), int(tcfg.qk_rope_head_dim)
    dn, dv = int(tcfg.qk_nope_head_dim), int(tcfg.v_head_dim or tcfg.dims_per_head)
    kept = min(s, int(tcfg.index_topk))
    ops = 2.0 * H * ((r + dr) + r) * kept + 2.0 * H * r * (dn + dv)
    nbytes = float(act_bytes * (r + dr) * kept)
    if full:
        ops += index_score_flops(tcfg, s)
        nbytes += float(act_bytes * int(tcfg.index_head_dim) * s)
    return {"flops": ops, "bytes": nbytes}


def sparse_decode(model, cycle: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The decode loop's index pass and attention, one phase: a row of ``q``
    prompt tokens and ``n`` new ones takes ``n - 1`` required steps (the
    prefill gives the first token), step ``i`` seeing its ``q + i + 1`` real
    slots, in every layer by its indexer type."""
    tcfg = model.tcfg
    if not getattr(tcfg, "index_topk", 0):
        return []
    kinds = [tcfg.layer_layout(i).indexer == "full" for i in range(model.n_layers)]
    total = {"flops": 0.0, "bytes": 0.0}
    memo: Dict[Any, Any] = {}
    for q, n in cycle["row_lengths"]:
        if (q, n) not in memo:
            row = {"flops": 0.0, "bytes": 0.0}
            for i in range(max(n - 1, 0)):
                for full in (True, False):
                    step = sparse_decode_row_step(tcfg, q + i + 1, full, model.act_bytes)
                    for key in row:
                        row[key] += step[key] * kinds.count(full)
            memo[q, n] = row
        for key in total:
            total[key] += memo[q, n][key]
    if total["flops"] <= 0.0:
        return []
    return [{"phase": "decode", **total}]
