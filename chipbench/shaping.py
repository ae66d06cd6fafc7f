"""Uneven response lengths as a property of the weights.

A random-init policy all but never samples eos, so uneven lengths have to be
made. They are made in the model, not in the sampler: for a model whose
published architecture has a bias on the output head (GPT-J), the eos column
of the head's kernel is zeroed and ``bias[eos]`` is set so that eos has
probability ``r`` (the traffic file's ``eos_rate``) at every step,

    bias[eos] = ln(r / (1 - r)) + L,

with ``L`` the log-sum-exp of the other logits. ``L`` barely varies with the
context at random init; it is taken as the mean over the last positions of
this run's prompts. The policy AND its hydra reference branch get the same
edit, so sampler, scoring forward, learner and plain reference all see one
model. The program's ``logit_mask`` is not used: it acts in the sampler only.
"""

import dataclasses
import math

import numpy as np


def shape_eos(trainer, traffic, seed):
    import jax
    import jax.numpy as jnp

    from chipbench import job
    from chipbench.checks import backbone_of

    r = float(traffic["eos_rate"])
    eos = int(trainer.tokenizer.eos_token_id)
    pad = int(trainer.tokenizer.pad_token_id)
    head = backbone_of(trainer.state.params)["lm_head"]
    if "bias" not in head:
        raise ValueError("eos_rate needs a model with a bias on its output head")

    prompts = job.make_prompts(traffic, seed)
    P = max(len(p) for p in prompts)
    ids = np.full((len(prompts), P), pad, np.int32)
    mask = np.zeros((len(prompts), P), np.int32)
    for i, p in enumerate(prompts):
        toks = trainer.tokenizer.encode(p)
        ids[i, P - len(toks):] = toks
        mask[i, P - len(toks):] = 1

    module = trainer.module

    def edit(params, ref_params, ids, mask):
        def zero_eos(head):
            return dict(head, kernel=head["kernel"].at[:, eos].set(0))

        def put(tree, new_head):
            if "backbone" in tree:
                return dict(tree, backbone=dict(tree["backbone"], lm_head=new_head))
            return dict(tree, lm_head=new_head)

        params = put(params, zero_eos(backbone_of(params)["lm_head"]))
        out = module.apply({"params": params}, ids, attention_mask=mask, logits_span=(P - 1, P))
        logits = out["logits"][:, 0].astype(jnp.float32)
        others = jnp.where(jnp.arange(logits.shape[-1]) == eos, -jnp.inf, logits)
        L = jnp.mean(jax.nn.logsumexp(others, axis=-1))
        value = math.log(r / (1.0 - r)) + L

        def set_bias(head):
            return dict(head, bias=head["bias"].at[eos].set(value.astype(head["bias"].dtype)))

        params = put(params, set_bias(backbone_of(params)["lm_head"]))
        ref_params = dict(ref_params, lm_head=set_bias(zero_eos(ref_params["lm_head"])))
        return params, ref_params, L

    params, ref_params, L = jax.jit(edit, donate_argnums=(0, 1))(
        trainer.state.params, trainer.ref_params, jnp.asarray(ids), jnp.asarray(mask)
    )
    trainer.state = dataclasses.replace(trainer.state, params=params)
    trainer.ref_params = ref_params
    return {"eos_rate": r, "eos_token": eos, "logsumexp_others": float(L),
            "ln_vocab_minus_1": math.log(trainer.tcfg.vocab_size - 1)}
