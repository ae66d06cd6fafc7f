"""The minicpm_sala family's own counts of required work.

Two kinds of layer, neither of which ``chipbench/flops.py``'s generic walk
counts right (it would charge every layer the causal score and value
products of ``num_attention_heads`` heads):

- a **lightning** layer (``tcfg.layer_layout(i).mixer == "lightning"``) has
  no score matrix at all. The recurrence REQUIRES, a head a token, the
  state's update ``k v^T`` and its read-out ``S^T q``: ``d x d``
  multiply-adds each, ``4 d^2`` operations (``d = lightning_head_dim``). What
  the program computes beyond that (the chunked form's products inside a
  chunk, ``ops/ssd.py``; the scan run again in the backward pass) is not
  required and lowers the share.
- a **sparse** layer attends, on a row of ``sparse_dense_len`` slots or
  more, over the keys of its chosen blocks only: ``sum_t kept(t)`` score and
  value pairs a row (``kept(t) = t + 1`` while the causal blocks number
  ``sparse_topk`` or fewer, then ``sparse_topk - 1`` whole blocks and the
  query's own up to itself), and the selection's scores of every complete
  kernel (``q . kbar_j`` a query head: ``2 d`` a pair of query and kernel).
  Unchosen tiles the kernels visit and mask are not required.

The selection has NO backward pass (``select_blocks`` puts ``stop_gradient``
on its inputs), and the hook's two buckets both have one: ``flops.py::
row_flops`` charges a layer at or above the lowest trained leaf ``matmuls``
once more and ``mix`` twice more. So the selection's forward work ``X`` is
entered as ``2X`` under ``attn/q_proj/kernel`` (a path of the tree, as every
key of ``matmuls`` has to be; under LoRA that leaf is frozen, so no weight
gradient is counted for it) and ``-X`` in ``mix``: the forward counts ``X``,
the activation-gradient pass ``2X - 2X = 0``, as ``costs/glm_moe_dsa.py``
enters its indexer.

Kernel costs: ``flash_fwd`` and ``flash_bwd`` are ``flops.py``'s phases on the
sparse layers alone and on the chosen pairs, with the selection's two bytes
a (query, block) read once; ``block_attn`` is both together (the restricted
softmax of the long passes: in this family the flash kernels run nowhere
else); ``lightning_scan`` is the recurrence of the long passes, forward and
backward, against its operations and the bytes of q, k, v read and o written
once.
"""

from typing import Any, Dict, List

import numpy as np

from chipbench import flops

SCORES_UNDER = ("attn", "q_proj", "kernel")


def is_lightning(tcfg, i: int) -> bool:
    return tcfg.layer_layout(i).mixer == "lightning"


def selects(tcfg, t: int) -> bool:
    return bool(tcfg.sparse_topk) and t >= int(tcfg.sparse_dense_len)


def chosen_pairs(tcfg, t: int) -> float:
    """(query, key) pairs a row of ``t`` tokens keeps on a sparse layer."""
    if not selects(tcfg, t):
        return flops.pairs(t, None)
    block, topk = int(tcfg.sparse_block), int(tcfg.sparse_topk)
    pos = np.arange(t, dtype=np.int64)
    kept = np.where(pos // block + 1 > topk, (topk - 1) * block + pos % block + 1, pos + 1)
    return float(kept.sum())


def kernel_pairs(tcfg, t: int) -> float:
    """(query, complete kernel) pairs the selection scores on a row of ``t`` tokens."""
    if not selects(tcfg, t):
        return 0.0
    kernel, stride = int(tcfg.sparse_kernel), int(tcfg.sparse_stride)
    pos = np.arange(t, dtype=np.int64)
    return float(np.maximum((pos - kernel + 1) // stride + 1, 0).sum())


def scan_flops(tcfg, t: int) -> float:
    """One row of ``t`` tokens through one lightning layer's recurrence, forward."""
    return 4.0 * int(tcfg.lightning_heads) * int(tcfg.lightning_head_dim) ** 2 * t


def selection_flops(tcfg, t: int) -> float:
    heads, _, d_qk, _ = flops.attention_dims(tcfg)
    return 2.0 * heads * d_qk * kernel_pairs(tcfg, t)


def attention_flops(tcfg, t: int) -> float:
    heads, _, d_qk, d_v = flops.attention_dims(tcfg)
    return 2.0 * heads * (d_qk + d_v) * chosen_pairs(tcfg, t)


def layer_forward(tcfg, i: int, layer_tree, t: int, stats: Dict[str, float]) -> Dict[str, Any]:
    """One row of ``t`` real tokens through layer ``i``, forward: the generic
    walk's matmuls (every 2-D ``kernel``, ``lora_a``, ``lora_b``), and for
    ``mix`` the recurrence of a lightning layer, or a sparse layer's products
    on its chosen pairs with the selection entered forward-only (above)."""
    matmuls = dict(flops.generic_layer_forward(tcfg, i, layer_tree, t, stats)["matmuls"])
    if is_lightning(tcfg, i):
        return {"matmuls": matmuls, "mix": scan_flops(tcfg, t)}
    scores = selection_flops(tcfg, t)
    matmuls[SCORES_UNDER] = matmuls.get(SCORES_UNDER, 0.0) + 2.0 * scores
    return {"matmuls": matmuls, "mix": attention_flops(tcfg, t) - scores}


def _selection_bytes(tcfg, t: int) -> float:
    """The selection a selecting pass reads: one bf16 a (KV head, query, block
    of keys), once (the kernels read a query block's again for every head of
    the group: not required)."""
    if not selects(tcfg, t):
        return 0.0
    return 2.0 * int(tcfg.kv_heads) * t * -(-t // int(tcfg.sparse_block))


def _sparse(model, layers) -> List[int]:
    return [i for i in layers if not is_lightning(model.tcfg, i)]


def flash_fwd(model, cycle: Dict[str, Any]) -> List[Dict[str, Any]]:
    """``flops.flash_fwd`` on the sparse layers and their chosen pairs."""
    tcfg, out = model.tcfg, []
    for name, layers, lengths, times, _ in flops._passes(model, cycle):
        n = len(_sparse(model, layers))
        ops = sum(times * attention_flops(tcfg, t) for t in lengths)
        nbytes = sum(times * (flops._flash_bytes(model, t, False) + _selection_bytes(tcfg, t)) for t in lengths)
        if n:
            out.append({"phase": name, "flops": n * ops, "bytes": n * nbytes})
    return out


def flash_bwd(model, cycle: Dict[str, Any]) -> List[Dict[str, Any]]:
    """``flops.flash_bwd`` likewise: four products for the forward's two."""
    tcfg = model.tcfg
    n = len(_sparse(model, [i for i in range(model.n_layers) if i >= model.lowest_trained]))
    lengths = [q + r for q, r in cycle["row_lengths"]]
    ops = sum(flops.MIX_BACKWARD * attention_flops(tcfg, t) for t in lengths)
    nbytes = sum(flops._flash_bytes(model, t, True) + _selection_bytes(tcfg, t) for t in lengths)
    if not n:
        return []
    return [{"phase": "train_backward", "flops": model.epochs * n * ops, "bytes": model.epochs * n * nbytes}]


def block_attn(model, cycle: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The restricted softmax of the long passes, forward and backward."""
    return flash_fwd(model, cycle) + [dict(p, phase="train_backward") for p in flash_bwd(model, cycle)]


def _scan_bytes(model, t: int, backward: bool) -> float:
    """One row, one layer: q, k, v read and o written once; the backward reads
    those and do and writes dq, dk, dv."""
    tcfg = model.tcfg
    width = int(tcfg.lightning_heads) * int(tcfg.lightning_head_dim)
    return float(model.act_bytes * t * width * (8 if backward else 4))


def lightning_scan(model, cycle: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The recurrence wherever whole rows go through a lightning layer
    (prefill, scoring with its reference branch, the steps' forward), and the
    steps' backward through every lightning layer at or above the lowest
    trained leaf (twice the forward's operations). The scan the backward runs
    again (``jax.checkpoint``) is the forward's, not required twice."""
    tcfg, out = model.tcfg, []
    for name, layers, lengths, times, _ in flops._passes(model, cycle):
        n = sum(is_lightning(tcfg, i) for i in layers)
        if n:
            out.append({"phase": name, "flops": n * sum(times * scan_flops(tcfg, t) for t in lengths),
                        "bytes": n * sum(times * _scan_bytes(model, t, False) for t in lengths)})
    n = sum(is_lightning(tcfg, i) for i in range(model.n_layers) if i >= model.lowest_trained)
    lengths = [q + r for q, r in cycle["row_lengths"]]
    if n:
        out.append({"phase": "train_backward",
                    "flops": model.epochs * n * sum(flops.MIX_BACKWARD * scan_flops(tcfg, t) for t in lengths),
                    "bytes": model.epochs * n * sum(_scan_bytes(model, t, True) for t in lengths)})
    return out
