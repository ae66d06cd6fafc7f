"""The traffic generator, checked without a chip.

    python3 -m pytest chipbench/tests -q        # from the root of the repository, JAX_PLATFORMS=cpu

A mix without ``prompt_alphabet`` draws what it always drew (the 26 letters
under ``builtin:bytes``: the checksums below are of the generator before the
key existed); a mix with it draws one token a character from that many
distinct characters, under a tokenizer that holds exactly them.
"""

import os
import zlib

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from chipbench import job  # noqa: E402

# crc32 of "\n".join(make_prompts(traffic, seed)) at the parent of PR 39's second round
LETTERS_AS_BEFORE = [
    ("grpo_decode", 11, 828412677),
    ("grpo_decode", 2907115103, 1095644261),
    ("ppo_hh", 11, 2912115862),
    ("ppo_hh", 2907115103, 1930110520),
    ("ppo_decode", 11, 597472876),
    ("ppo_decode", 2907115103, 3496674974),
    ("grpo_tail", 2907115103, 1095644261),
]


@pytest.mark.parametrize("name,seed,crc", LETTERS_AS_BEFORE)
def test_a_mix_without_the_key_draws_the_letters_it_drew(name, seed, crc):
    traffic = job.load_json("traffic", name)
    assert "prompt_alphabet" not in traffic
    prompts = job.make_prompts(traffic, seed)
    assert zlib.crc32("\n".join(prompts).encode()) == crc


def _config(traffic):
    config_file = job.load_config("smallthinker-21b-a3b-l4e16", toy=True)
    return job.build_config(config_file, traffic, 7, toy=True, ckpt_dir="/nonexistent")


def test_a_mix_without_the_key_keeps_the_programs_tokenizer():
    cfg = _config(job.load_json("traffic", "grpo_decode"))
    assert cfg.tokenizer.tokenizer_path == "builtin:bytes"


@pytest.mark.parametrize("seed", [11, 2907115103])
def test_ctx8k_prompts_are_one_token_a_character_from_2048(seed):
    from trlx_tpu.data.tokenizer import from_config

    traffic = job.load_json("traffic", "grpo_ctx8k")
    n = traffic["prompt_alphabet"]
    assert n == 2048
    tok = from_config(_config(traffic).tokenizer)
    assert (tok.vocab_size, tok.eos_token_id, tok.pad_token_id) == (n + 3, n + 1, n + 2)
    prompts = job.make_prompts(traffic, seed)
    assert prompts == job.make_prompts(traffic, seed)  # the same seed, the same inputs
    ids = [tok.encode(p) for p in prompts]
    assert [len(row) for row in ids] == [6144, 6144]
    assert all(0 <= i < n for row in ids for i in row)
    # 12,288 draws from 2048: nearly every token appears (26 did before)
    assert len({i for row in ids for i in row}) > 2000
    assert tok.decode(ids[0]) == prompts[0]
    assert job.eval_prompt(traffic, seed)[0] in prompts


def test_another_seed_draws_other_prompts_of_the_same_sizes():
    traffic = job.load_json("traffic", "grpo_ctx8k")
    a, b = job.make_prompts(traffic, 1), job.make_prompts(traffic, 2)
    assert a != b and sorted(map(len, a)) == sorted(map(len, b))


@pytest.mark.parametrize("n", [0, 26, 20001])
def test_an_alphabet_out_of_range_is_refused(n):
    traffic = dict(job.load_json("traffic", "grpo_decode"), prompt_alphabet=n)
    with pytest.raises(ValueError, match="prompt_alphabet"):
        job.make_prompts(traffic, 3)
