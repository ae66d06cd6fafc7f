"""From a cell's two data files to the job the program runs.

A cell is ``workloads[i]`` of ``BENCHMARK.json``: ``config`` names
``chipbench/configs/<config>.json`` (the model as it is run, the mesh) and
``traffic`` names ``chipbench/traffic/<traffic>.json`` (the method, its
sizes, the lengths). Both are data; this module is the one general reader.
It builds the ``TRLConfig``, the prompts and the reward function, all from
``--seed``, and refuses a file that tries to choose a schedule or a kernel.
"""

import json
import os
import zlib
from typing import Any, Dict, List, Tuple

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# A cell pins the job, never the mechanism: these stay at the program's
# defaults so that a PR which changes a default shows in the cells that
# exist. A dotted key that equals one of these, or lies under it, is refused.
MECHANISM_KEYS = (
    "engine",
    "async_rl",
    "serve",
    "resilience",
    "train.continuous_batching",
    "train.continuous_batching_segment",
    "train.rollout_pipeline_depth",
    "train.prefetch_batches",
    "method.loss_kernel",
    "method.iw_correction",
    "model.draft_model_path",
    "parallel.remat",
    "parallel.scan_layers",
)
MECHANISM_LEAVES = ("attention_impl", "logit_mask")

# What a traffic file's "job" group may set (README.md, "Traffic").
TRAFFIC_SECTIONS = ("method", "train", "model", "optimizer", "scheduler")
# What a configuration file's "job" group may set.
CONFIG_SECTIONS = ("model", "parallel")


def load_json(kind: str, name: str) -> Dict[str, Any]:
    path = os.path.join(ROOT, kind, f"{name}.json")
    with open(path) as f:
        return json.load(f)


def load_config(name: str, toy: bool = False) -> Dict[str, Any]:
    """A configuration file holds the model's published keys at its top level
    (the changed ones listed under ``reduced``) and the harness's own keys in
    the group ``chipbench``. ``toy`` overlays the rehearsal's sizes."""
    raw = load_json("configs", name)
    meta = raw.pop("chipbench")
    if meta["name"] != name:
        raise ValueError(f"configs/{name}.json names itself {meta['name']!r}")
    published = dict(raw)
    if toy:
        published.update(meta["toy"].get("published", {}))
    return dict(meta, published=published)


def load_benchmark() -> Dict[str, Any]:
    with open(os.path.join(os.path.dirname(ROOT), "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(name: str) -> Dict[str, Any]:
    for cell in load_benchmark()["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def flatten(tree: Dict[str, Any], prefix: str = "") -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for k, v in tree.items():
        key = f"{prefix}.{k}" if prefix else k
        if isinstance(v, dict) and not key.endswith("kwargs"):
            out.update(flatten(v, key))
        else:
            out[key] = v
    return out


def refuse_mechanisms(job: Dict[str, Any], where: str, sections: Tuple[str, ...]) -> None:
    for section in job:
        if section not in sections:
            raise ValueError(f"{where}: section {section!r} is not one of {sections}")
    for key, value in flatten(job).items():
        for banned in MECHANISM_KEYS:
            if key == banned or key.startswith(banned + "."):
                raise ValueError(
                    f"{where} sets {key}: a cell pins the job, never the "
                    "schedule or the kernel (chipbench/README.md)"
                )
        leaves = [key.rsplit(".", 1)[-1]]
        if isinstance(value, dict):
            leaves += list(value)
        for leaf in leaves:
            if leaf in MECHANISM_LEAVES:
                raise ValueError(f"{where} sets {leaf} (under {key}): refused")


def config_seed(seed: int) -> int:
    """``--seed`` may exceed 31 bits; the program's seeds are int32."""
    return int(seed) % 2147483647


# ---------------------------------------------------------------------------
# prompts and reward
# ---------------------------------------------------------------------------


def prompt_lengths(spec: Dict[str, Any], n: int) -> List[int]:
    """The multiset of prompt lengths of one cycle. It depends on the traffic
    file alone, never on the seed: every seed gets the same sizes in another
    order, so the padded shapes (and the work) are the same in every run."""
    kind = spec["kind"]
    if kind == "fixed":
        return [int(spec["tokens"])] * n
    if kind == "geometric_grid":
        # n lengths spaced geometrically from min to max: heavy toward short,
        # the longest always present (it sets the padded width of the chunk)
        lo, hi = int(spec["min"]), int(spec["max"])
        grid = np.geomspace(lo, hi, n)
        return [int(round(x / 8.0)) * 8 for x in grid]
    raise ValueError(f"unknown prompt length kind {kind!r}")


LETTERS = "abcdefghijklmnopqrstuvwxyz"
ALPHABET_BASE = 0x4E00  # a block of 20,992 assigned code points, no surrogates


def prompt_alphabet(traffic: Dict[str, Any]) -> str:
    """The characters a prompt is drawn from, one token each. Without the
    traffic file's ``prompt_alphabet`` the 26 letters under ``builtin:bytes``.
    With ``prompt_alphabet: n`` (26 < n <= 20000) n distinct characters under
    ``builtin:chars:<those characters>`` (ids 0 to n-1, then bos, eos, pad),
    for a mix whose work follows WHICH tokens a row holds: routing over held
    experts on 26 distinct vectors swings with the seed's weights, on a
    transcript's thousands of distinct tokens it does not (PERF.md, PR 39)."""
    n = traffic.get("prompt_alphabet")
    if n is None:
        return LETTERS
    n = int(n)
    if not 26 < n <= 20000:
        raise ValueError(f"traffic/{traffic['name']}.json: prompt_alphabet {n} not in 27..20000")
    return "".join(chr(ALPHABET_BASE + i) for i in range(n))


def make_prompts(traffic: Dict[str, Any], seed: int) -> List[str]:
    """Strings of exact token length (one character of the alphabet is one
    token), one cycle's worth, order and characters from the seed."""
    n = int(traffic["prompts_per_cycle"])
    rng = np.random.RandomState(config_seed(seed))
    lengths = prompt_lengths(traffic["prompt_length"], n)
    rng.shuffle(lengths)
    alphabet = prompt_alphabet(traffic)
    return ["".join(alphabet[c] for c in rng.randint(0, len(alphabet), size=L)) for L in lengths]


def eval_prompt(traffic: Dict[str, Any], seed: int) -> List[str]:
    """The fewest eval prompts the loop accepts: one, and always the longest
    of the cycle, so that ``evaluate()``'s generate program has one shape on
    every seed and is found in the compile cache."""
    return [max(make_prompts(traffic, seed), key=len)]


def make_reward_fn(group_size: int):
    """A cheap deterministic host function of the output. Row ``i`` of a call
    scores ``(i mod group_size) + crc32(output) / 2**32``: the program hands
    GRPO groups over as contiguous rows, so within a group the integer parts
    are 0..G-1, all different, on every seed — no group has zero spread, and
    the fractional part still makes the score depend on what was generated."""
    g = max(int(group_size), 1)

    def reward_fn(samples, prompts, outputs, **kwargs):
        return [
            float(i % g) + zlib.crc32(o.encode("utf-8", "replace")) / 4294967296.0
            for i, o in enumerate(outputs)
        ]

    return reward_fn


# ---------------------------------------------------------------------------
# the TRLConfig
# ---------------------------------------------------------------------------


def build_config(config_file: Dict[str, Any], traffic: Dict[str, Any], seed: int,
                 toy: bool, ckpt_dir: str):
    """The job's ``TRLConfig``: the program's default for the method, then the
    traffic file's job keys, then the configuration file's. ``toy`` swaps in
    the configuration's rehearsal model (CPU walk of the same code path)."""
    from trlx_tpu.data import default_configs

    method = traffic["method"]
    base = {"ppo": default_configs.default_ppo_config,
            "grpo": default_configs.default_grpo_config}[method]()

    t_job = json.loads(json.dumps(traffic.get("job", {})))
    c_job = json.loads(json.dumps(config_file["job"]))
    refuse_mechanisms(t_job, f"traffic/{traffic['name']}.json", TRAFFIC_SECTIONS)
    refuse_mechanisms(c_job, f"configs/{config_file['name']}.json", CONFIG_SECTIONS)
    if toy:
        c_job["model"].update(config_file["toy"]["model"])

    new_tokens = int(traffic["max_new_tokens"])
    lengths = prompt_lengths(traffic["prompt_length"], int(traffic["prompts_per_cycle"]))
    gen = dict(max_new_tokens=new_tokens, top_k=0, top_p=1.0, do_sample=True,
               temperature=float(traffic.get("temperature", 1.0)))
    if not traffic.get("eos_rate"):
        # fixed-length traffic: eos is blocked for the whole response, so every
        # row has exactly max_new_tokens and the cycle's work is one number
        gen["min_new_tokens"] = new_tokens
    s = config_seed(seed)
    derived = {
        "train": dict(
            seq_length=max(lengths) + new_tokens,
            seed=s,
            total_steps=1_000_000_000,  # never the binding limit: the
            epochs=1_000_000,           # harness's tracker ends the run
            eval_interval=1_000_000_000,
            checkpoint_interval=1_000_000_000,
            checkpoint_dir=ckpt_dir,
            tracker=None,
            eval_batch_size=1,
        ),
        "method": dict(gen_kwargs=gen),
    }
    if traffic.get("prompt_alphabet") is not None:
        derived["tokenizer"] = dict(tokenizer_path="builtin:chars:" + prompt_alphabet(traffic))
    cfg = base.evolve(**derived)
    for job in (t_job, c_job):
        if job:
            cfg = cfg.evolve(**job)
    return cfg


def cycle_shape(cfg, traffic: Dict[str, Any]) -> Dict[str, int]:
    """Sizes the harness needs to recognise a whole cycle in the tracker
    stream: rollouts a cycle, optimizer steps a cycle, padded widths."""
    rollouts = int(cfg.method.num_rollouts)
    batch = int(cfg.train.batch_size)
    lengths = prompt_lengths(traffic["prompt_length"], int(traffic["prompts_per_cycle"]))
    return {
        "rollouts": rollouts,
        "steps": int(cfg.method.ppo_epochs) * (rollouts // batch),
        "batch": batch,
        "prompt": max(lengths),
        "new": int(traffic["max_new_tokens"]),
        "group": int(getattr(cfg.method, "group_size", 1)),
    }


def check_published_widths(cfg, config_file: Dict[str, Any]) -> None:
    """The model the program resolves must have the sizes the configuration
    file publishes (``maps``: published key -> ``TransformerConfig`` field)."""
    from trlx_tpu.models.builder import resolve_transformer_config

    tcfg, _ = resolve_transformer_config(cfg.model, cfg.parallel)
    for key, field in config_file["maps"].items():
        want, got = config_file["published"][key], getattr(tcfg, field)
        if want is None:
            continue
        if isinstance(want, float):
            same = abs(float(got) - want) <= 1e-12 * max(abs(want), 1.0)
        else:
            same = got == want
        if not same:
            raise ValueError(
                f"configs/{config_file['name']}.json publishes {key}={want}, "
                f"the program resolves {field}={got}"
            )
