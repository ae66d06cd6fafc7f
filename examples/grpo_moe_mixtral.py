"""GRPO on a mixture-of-experts (Mixtral-family) policy.

Doubly beyond the reference (trlx v0.6.0 has neither GRPO nor any MoE
support): critic-free group-relative RLHF driving a sparse-expert backbone.
The expert weights shard over the mesh's ``expert`` axis (expert
parallelism — token dispatch/combine ride compiler-inserted all_to_alls),
the fp32 top-k router's Switch load-balance and z losses ride the GRPO
objective via ``model_extra_kwargs`` coefficients, and everything else —
grouped rollouts, in-loss KL, sampling — is the stock GRPO machinery.

Defaults to the tiny ``builtin:mixtral-test`` preset so the script runs
anywhere (CPU mesh included); point ``MODEL_PATH`` at a local Mixtral
checkpoint directory to RLHF the real 8x7B (import is exact —
``tests/test_hf_export.py::test_roundtrip_exact_logits[mixtral]``).

Capacity note: HF import routes without a capacity bound
(``moe_capacity_factor = 0``, dropless grouped matmuls), so imported
checkpoints reproduce HF logits exactly; that path runs the experts on one
device. For expert-parallel *training* this script overrides it to
``MOE_CAPACITY`` (default 2.0): the one-hot dispatch with a static capacity,
whose overflow tokens are dropped — standard MoE training behavior; the
Switch load-balance loss keeps drops rare (``moe/dropped_frac`` in the step
stats says how rare).
"""

import os

import trlx_tpu.trlx as trlx
from trlx_tpu.data.default_configs import default_grpo_config

from sentiment_util import get_positive_sentiment_fn, review_prompts


def resolve_model():
    path = os.environ.get("MODEL_PATH")
    if path:
        return path, path
    return "builtin:mixtral-test", "builtin:bytes"


def main(hparams=None):
    model_path, tokenizer_path = resolve_model()
    sentiment = get_positive_sentiment_fn()

    extra = dict(router_aux_coef=0.01, router_z_coef=0.001)
    if os.environ.get("MODEL_PATH"):
        # Override the dropless import default (one device) with a capacity
        # the `expert` mesh axis can dispatch; see the module docstring.
        extra["moe_capacity_factor"] = float(os.environ.get("MOE_CAPACITY", 2.0))

    config = default_grpo_config().evolve(
        train=dict(
            seq_length=128,
            batch_size=32,
            total_steps=2000,
            eval_interval=100,
            checkpoint_interval=10000,
            checkpoint_dir="ckpts/grpo_moe_mixtral",
        ),
        model=dict(
            model_path=model_path,
            # router-loss weights are model knobs (TransformerConfig);
            # raise router_aux_coef if expert load collapses during RL
            model_extra_kwargs=extra,
        ),
        tokenizer=dict(tokenizer_path=tokenizer_path),
        # expert=2 partitions the experts; scale with the pod (e.g. a v4-32
        # runs data=2 fsdp=2 model=2 expert=2); -1 infers the data axis
        parallel=dict(data=-1, expert=int(os.environ.get("EXPERT_PARALLEL", 1))),
        method=dict(
            gen_kwargs=dict(max_new_tokens=40, top_k=0, top_p=1.0, do_sample=True)
        ),
    )
    if hparams:
        from trlx_tpu.data.configs import TRLConfig

        config = TRLConfig.update(config, hparams)

    def reward_fn(samples, prompts, outputs, **kwargs):
        return sentiment(samples)

    return trlx.train(
        reward_fn=reward_fn,
        prompts=review_prompts(256, seed=0),
        eval_prompts=review_prompts(64, seed=1),
        config=config,
    )


if __name__ == "__main__":
    import json
    import sys

    main(json.loads(sys.argv[1]) if len(sys.argv) > 1 else None)
