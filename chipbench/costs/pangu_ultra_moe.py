"""The pangu_ultra_moe family's own counts of required work.

The generic walk of ``chipbench/flops.py`` reads this family's tree as it is
(every projection of ``LatentAttention`` and the shared expert are 2-D
``kernel`` leaves at ``2ab`` a token, the routed experts 3-D leaves at ``2ab x
k x held_frac``, score and value products from ``tcfg.dims_per_head`` 192 and
``tcfg.v_head_dim`` 128), so there is no ``layer_forward`` here. What the walk
has no count for is the decode loop's attention over the latent cache, which
no flash kernel sees: ``latent_decode`` below, for ``latent_decode_roofline``.
"""

from typing import Any, Dict, List


def latent_decode_row_step(tcfg, s: int, act_bytes: int) -> Dict[str, float]:
    """One row, one layer, one decode step that sees ``s`` slots: the scores
    over the latent and the one roped key (``2 H (r + dr) s``), ``sum p c``
    (``2 H r s``), and the two folds through ``kv_b_proj`` (the query's
    no-rope part into the latent, ``2 H dn r``, and the result out of it, ``2
    H r dv``); bytes: the ``s`` slots of the latent and the roped key read
    once and the step's own slot written. The same work whatever implements
    it (XLA einsums today, a kernel later); ``kv_b_proj``'s matrix, read once
    a step a layer whatever the rows, is left out, so the share reads low by
    it and never high."""
    H, r, dr = int(tcfg.num_heads), int(tcfg.kv_lora_rank), int(tcfg.qk_rope_head_dim)
    dn, dv = int(tcfg.qk_nope_head_dim), int(tcfg.v_head_dim or tcfg.dims_per_head)
    return {"flops": 2.0 * H * ((r + dr) + r) * s + 2.0 * H * r * (dn + dv),
            "bytes": float(act_bytes * (r + dr) * (s + 1))}


def latent_decode(model, cycle: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The decode loop's attention over the latent cache, one phase: a row of
    ``q`` prompt tokens and ``n`` new ones takes ``n - 1`` required steps (the
    prefill gives the first token), step ``i`` seeing its ``q + i + 1`` real
    slots, in every layer."""
    tcfg = model.tcfg
    if not getattr(tcfg, "kv_lora_rank", 0):
        return []
    flops = nbytes = 0.0
    memo: Dict[Any, Any] = {}
    for q, n in cycle["row_lengths"]:
        if (q, n) not in memo:
            steps = [latent_decode_row_step(tcfg, q + i + 1, model.act_bytes) for i in range(max(n - 1, 0))]
            memo[q, n] = (sum(s["flops"] for s in steps), sum(s["bytes"] for s in steps))
        flops += memo[q, n][0]
        nbytes += memo[q, n][1]
    if flops <= 0.0:
        return []
    return [{"phase": "decode", "flops": model.n_layers * flops, "bytes": model.n_layers * nbytes}]
