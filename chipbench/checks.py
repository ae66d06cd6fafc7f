"""What decides ``correct``: the same four checks in every cell, on samples
whose size does not depend on the cell's traffic or on the seed.

1. model against the plain reference: relative L2 error of the logits, per
   sequence, on ``ROWS`` sequences and at most ``SPAN`` positions each;
2. decode through the cache against the reference's full forward: mean
   absolute difference between the logprobs the sampler recorded and the
   reference's logprob of the same tokens, on the first ``RESP`` response
   positions of the same rows;
3. the update: every ``losses/*`` finite, a trainable leaf changed, no
   program recompiled in the window, and policy and hydra reference branch
   (which start equal) at a KL of zero to rounding before the first update;
4. the rollouts: asked for, delivered, finite.

Checks 1 and 2 are aggregates over a fixed number of values (4 sequences x
256 positions; 4 x 128 logprobs), never a maximum over everything a cell
generates: bf16 logprobs near ``-ln(vocab)`` are spaced 2**-4 apart
(PERF.md section 6, PR 22), so a maximum grows with the number of tokens
compared and the longest cell would fail first, on some seeds only.

The tolerances are in ``chipbench/tolerances/<config>.json``, measured on
the chip on eight seeds and against a planted fault (``seedcheck.py``).
"""

import importlib
import json
import os
from typing import Any, Dict, Optional

import numpy as np

ROWS = 4      # sequences compared
SPAN = 256    # positions per sequence whose logits are compared
RESP = 128    # response positions per sequence whose logprobs are compared

HERE = os.path.dirname(os.path.abspath(__file__))


def load_tolerances(config_name: str) -> Dict[str, float]:
    with open(os.path.join(HERE, "tolerances", f"{config_name}.json")) as f:
        return {k: float(v) for k, v in json.load(f)["tolerances"].items()}


def backbone_of(params):
    return params["backbone"] if "backbone" in params else params


def sample_rows(n_rows: int) -> np.ndarray:
    """ROWS row indices spread evenly over the chunk."""
    return np.unique(np.linspace(0, n_rows - 1, ROWS).round().astype(int))


def model_checks(trainer, config_file: Dict[str, Any], gen_out, fault: Optional[str] = None
                 ) -> Dict[str, float]:
    """Checks 1 and 2 on the captured first chunk of a collection. Must run
    before the next update: the sampler's logprobs belong to the parameters
    the trainer still holds."""
    import jax
    import jax.numpy as jnp

    ref = importlib.import_module(f"chipbench.reference.{config_file['family']}")
    dims = config_file["published"]

    rows = sample_rows(int(gen_out.sequences.shape[0]))
    P = int(gen_out.prompt_mask.shape[1])
    R = min(RESP, int(gen_out.response_tokens.shape[1]))
    T = P + R
    lo = max(T - SPAN, 0)
    seqs = np.asarray(jax.device_get(gen_out.sequences))[rows, :T]
    p_mask = np.asarray(jax.device_get(gen_out.prompt_mask))[rows]
    r_mask = np.asarray(jax.device_get(gen_out.response_mask))[rows, :R]
    r_tok = np.asarray(jax.device_get(gen_out.response_tokens))[rows, :R]
    r_lp = np.asarray(jax.device_get(gen_out.response_logprobs), np.float32)[rows, :R]
    mask = np.concatenate([p_mask, r_mask], axis=1).astype(np.int32)

    params = trainer.state.params
    ref_logits = ref.logits(backbone_of(params), dims, seqs, mask, (lo, T), fault=fault)

    module = trainer.module

    @jax.jit
    def system_logits(p, ids, m):
        out = module.apply({"params": p}, ids, attention_mask=m, logits_span=(lo, T))
        return out["logits"].astype(jnp.float32)

    sys_logits = system_logits(params, jnp.asarray(seqs), jnp.asarray(mask))

    m = jnp.asarray(mask[:, lo:T, None], jnp.float32)
    num = jnp.sqrt(jnp.sum(((sys_logits - ref_logits) * m) ** 2, axis=(1, 2)))
    den = jnp.sqrt(jnp.sum((ref_logits * m) ** 2, axis=(1, 2)))
    rel_l2 = np.asarray(num / den)

    # position P-1+j predicts response token j
    pred = jax.nn.log_softmax(ref_logits[:, P - 1 - lo : P - 1 - lo + R], axis=-1)
    ref_lp = np.asarray(jnp.take_along_axis(pred, jnp.asarray(r_tok)[..., None], axis=-1)[..., 0])
    w = r_mask.astype(np.float32)
    mad = float((np.abs(ref_lp - r_lp) * w).sum() / max(w.sum(), 1.0))
    return {
        "logits_rel_l2": float(rel_l2.max()),
        "logits_rel_l2_rows": [float(x) for x in rel_l2],
        "decode_logprob_mad": mad,
        "compared_logprobs": int(w.sum()),
    }


def _bit_sums(params):
    import jax
    import jax.numpy as jnp

    def bits(x):
        uint = {2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
        return jnp.sum(jax.lax.bitcast_convert_type(x, uint).astype(jnp.uint32))

    return jax.tree_util.tree_map(bits, params)


def leaf_fingerprints(params):
    """One exact integer per leaf: the wrapping sum of its bits. A single
    changed element changes it, which a float sum over millions would not."""
    import jax

    prints = jax.jit(_bit_sums)(params)
    return [int(x) for x in jax.tree_util.tree_leaves(jax.device_get(prints))]


def store_failures(trainer, asked: int) -> int:
    """Rollouts of one collection not delivered to the learner, or delivered
    with a non-finite logprob, value, reward or advantage."""
    elems = list(trainer.store.history)
    bad = 0
    for e in elems:
        for name in ("logprobs", "values", "rewards", "ref_logprobs", "advantage"):
            v = getattr(e, name, None)
            if v is not None and not np.isfinite(np.asarray(v, np.float64)).all():
                bad += 1
                break
    return max(asked - len(elems), 0) + bad


BOOLEAN_CHECKS = ("losses_finite", "leaf_changed", "no_recompile", "no_compile_in_window",
                  "rollouts_delivered")


def compared(values: Dict[str, Any], tolerances: Dict[str, float]) -> Dict[str, Dict[str, Any]]:
    """Each number ``verdict`` compares beside its limit, under short plain
    names: the result line's last key and the run's last lines on standard
    error. A yes/no check reads 1 or 0 and has to be 1."""
    out = {}
    for name, tol in tolerances.items():
        v = values.get(name)
        finite = isinstance(v, (int, float)) and bool(np.isfinite(v))
        out[name] = {"value": float(v) if finite else None, "at_most": tol}  # no NaN in the line
    for name in BOOLEAN_CHECKS:
        out[name] = {"value": int(bool(values.get(name, False))), "at_least": 1}
    return out


def verdict(values: Dict[str, Any], tolerances: Dict[str, float]) -> bool:
    """Print one line per failed check (which, the value, the tolerance) and
    return whether all passed."""
    ok = True
    for name, tol in tolerances.items():
        value = values.get(name)
        if value is None or not np.isfinite(value) or value > tol:
            print(json.dumps({"check_failed": name, "value": value, "tolerance": tol}), flush=True)
            ok = False
    for name in BOOLEAN_CHECKS:
        if not values.get(name, False):
            print(json.dumps({"check_failed": name, "value": values.get(name),
                              "detail": values.get(name + "_detail")}), flush=True)
            ok = False
    return ok
