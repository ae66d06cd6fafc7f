"""Operations the algorithm requires, from shapes.

Required means: what the forward and backward passes of one optimizer step
need on the REAL tokens of its rows, with frozen layers forward only and
nothing recomputed. XLA's cost analysis of the compiled program (the
program's own ``throughput/mfu``) counts padded and recomputed work too,
which is why the yardstick does not use it.

Per real token a matmul with an ``[a, b]`` kernel costs ``2ab``; causal
attention over a sequence of ``t`` real tokens costs ``2 * heads * head_dim *
t**2`` per layer (QK^T and AV, half of the full square each). A backward pass
through a layer costs twice its forward (gradients of activations and of
weights). The output head runs on the response positions only, as the
program's ``logits_span`` does.
"""

from typing import Any, Dict

import numpy as np


def model_sizes(trainer) -> Dict[str, int]:
    """Matmul parameters per layer, attention width and head size, read from
    the trainer's own parameter tree (any dense decoder, no per-family code)."""
    from chipbench.checks import backbone_of

    bb = backbone_of(trainer.state.params)
    layer = bb["h_0"]
    import jax

    kernels = [x for path, x in jax.tree_util.tree_flatten_with_path(layer)[0]
               if getattr(path[-1], "key", None) == "kernel" and x.ndim == 2]
    head = bb["lm_head"]["kernel"] if "lm_head" in bb else bb["wte"]["embedding"]
    return {
        "layer_matmul_params": int(sum(int(np.prod(k.shape)) for k in kernels)),
        "attn_width": int(layer["attn"]["q_proj"]["kernel"].shape[1]),
        "head_params": int(np.prod(head.shape)),
        "layers": int(trainer.tcfg.num_layers),
        "unfrozen": int(trainer.num_layers_unfrozen) if trainer.num_layers_unfrozen > 0
        else int(trainer.tcfg.num_layers),
    }


def step_flops(sizes: Dict[str, int], query_len: int, response_len: int) -> float:
    """Forward and backward FLOPs one row of an optimizer step requires."""
    t = query_len + response_len
    layer = 2.0 * sizes["layer_matmul_params"] * t + 2.0 * sizes["attn_width"] * t * t
    head = 2.0 * sizes["head_params"] * response_len
    return layer * (sizes["layers"] + 2 * sizes["unfrozen"]) + 3.0 * head


def learn_flops_of_cycle(trainer, cycle: Dict[str, Any]) -> float:
    """Every delivered rollout of the cycle is learned from ``ppo_epochs`` times."""
    sizes = model_sizes(trainer)
    epochs = int(trainer.config.method.ppo_epochs)
    return epochs * sum(step_flops(sizes, q, r) for q, r in cycle["row_lengths"])
