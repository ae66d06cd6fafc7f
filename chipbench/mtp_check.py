"""The next-token-prediction module against the reference, on the chip.

    python3 -m chipbench.mtp_check --workload kexaone236b_ppo_selfdraft --seed <n>

``run.py --checks-only`` (set-up, the warm-up cycle, checks 1 to 3 on the
collection that follows) with two more readings taken where check 2 takes
its own, on the same rows and response positions of the same rollouts:

- ``mtp_logits_rel_l2``: the module's logits (``CausalTransformer.draft`` on
  the stack's own hidden states, the program's dtype) against the reference's
  ``mtp_logits`` (float32, ``highest``), relative L2 per row, the worst row;
- ``accept_counted_pct`` against ``accept_reference_pct``: the share of its
  proposals the sampler kept in that collection (``rollout/spec_acceptance_rate``,
  all rows and rounds) against the reference's mean ``sum_x min(p(x), q(x))``,
  ``p`` the stack's distribution over a response token and ``q`` the
  module's, which is what exact rejection sampling accepts in expectation.

They decide nothing (``correct`` is checks 1 to 4 alone: PPO reads none of the
module): they go into ``chipbench/tolerances/<config>.json`` beside the
eight-seed arithmetic. A reference without ``mtp_logits`` has no module to
compare and the run is ``--checks-only`` as it was.
"""

import importlib
import sys

import numpy as np

from chipbench import checks, run


def module_readings(trainer, config_file, gen_out):
    import jax
    import jax.numpy as jnp

    ref = importlib.import_module(f"chipbench.reference.{config_file['family']}")
    if not hasattr(ref, "mtp_logits"):
        return {}
    rows = checks.sample_rows(int(gen_out.sequences.shape[0]))
    P = int(gen_out.prompt_mask.shape[1])
    R = min(checks.RESP, int(gen_out.response_tokens.shape[1]))
    T = P + R
    seqs = np.asarray(jax.device_get(gen_out.sequences))[rows, :T]
    mask = np.concatenate([np.asarray(jax.device_get(gen_out.prompt_mask))[rows],
                           np.asarray(jax.device_get(gen_out.response_mask))[rows, :R]], axis=1).astype(np.int32)
    params, module = trainer.state.params, trainer.module
    backbone, dims = checks.backbone_of(params), config_file["published"]
    # response token j sits at slot P + j: p from the stack at P + j - 1, q from the module's entry P + j - 2
    ref_p = ref.logits(backbone, dims, seqs, mask, (P - 1, T - 1))
    ref_q = ref.mtp_logits(backbone, dims, seqs, mask, (P - 2, T - 2))

    @jax.jit
    def system(p, ids, m):
        out = module.apply({"params": p}, ids, attention_mask=m, logits_span=(P - 1, T - 1))
        q = module.apply({"params": p}, out["pre_norm_hidden"][:, :-1], ids[:, 1:], attention_mask=m[:, :-1],
                         logits_span=(P - 2, T - 2), method="draft")["logits"]
        return out["logits"].astype(jnp.float32), q.astype(jnp.float32)

    sys_p, sys_q = system(params, jnp.asarray(seqs), jnp.asarray(mask))
    rel = jnp.sqrt(jnp.sum((sys_q - ref_q) ** 2, axis=(1, 2)) / jnp.sum(ref_q**2, axis=(1, 2)))
    overlap = lambda a, b: float(jnp.mean(jnp.sum(jnp.minimum(jax.nn.softmax(a), jax.nn.softmax(b)), axis=-1)))
    return {
        "mtp_logits_rel_l2": float(jnp.max(rel)),
        "mtp_logits_rel_l2_rows": [float(x) for x in rel],
        "accept_counted_pct": 100.0 * float(trainer.last_spec_stats["rollout/spec_acceptance_rate"]),
        "accept_counted_proposals": int(trainer.last_spec_stats["rollout/draft_proposed"]),
        "accept_reference_pct": 100.0 * overlap(ref_p, ref_q),
        "accept_system_pct": 100.0 * overlap(sys_p, sys_q),  # the same mean from the program's own p and q
        "accept_positions": int(rows.size * R),
    }


def main(argv=None) -> int:
    model_checks = checks.model_checks

    def with_module(trainer, config_file, gen_out, fault=None):
        run.say(mtp_check=module_readings(trainer, config_file, gen_out))
        return model_checks(trainer, config_file, gen_out, fault=fault)

    checks.model_checks = with_module
    try:
        return run.main(list(sys.argv[1:] if argv is None else argv) + ["--seconds", "0", "--trace", "0", "--checks-only"])
    finally:
        checks.model_checks = model_checks


if __name__ == "__main__":
    sys.exit(main())
