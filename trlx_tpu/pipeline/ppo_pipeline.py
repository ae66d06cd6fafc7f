"""PPO rollout storage.

Behavioral parity target: ``trlx/pipeline/ppo_pipeline.py:13-80`` — a replay
buffer of per-sample experiences with a left-pad-queries / right-pad-responses
collator and JSON rollout export. Collation pads to bucketed lengths (static
shapes for the jitted train step).
"""

import json
import os
from typing import List, Optional, Sequence, Tuple, Union

import numpy as np

from trlx_tpu.data.ppo_types import PPORLBatch, PPORLElement
from trlx_tpu.pipeline import BaseRolloutStore, BatchLoader
from trlx_tpu.pipeline.offline_pipeline import pad_rows, round_up

# The smallest rung of the learner's ladder of widths. Every rung is one more
# train-step program to trace, lower and load in the first cycle (5.3 s each on
# a v5e's host), and set-up is a metric with a bound: 128 gave the PPO cells
# +47% samples/s at a set-up 9.5% over the parent's, 256 +43% at 8% under it,
# 512 +27% (PERF.md section 6, PR 30). A constant with its measurement, not a
# setting.
LADDER_BASE = 256

# an int pins the width (longer rows are cut), None pads to the batch's own
# longest row rounded up to ``pad_multiple``, a ladder pads to one of its rungs
PadLength = Union[int, Sequence[int], None]


def length_ladder(cap: int, pad_multiple: int = 8) -> Tuple[int, ...]:
    """The widths a learner's minibatch may be padded to when no row is
    longer than ``cap``: ``LADDER_BASE * 2**k`` below ``cap``, then ``cap``
    (rounded up to ``pad_multiple``). 896 gives 256, 512, 896. Empty
    where the job states no positive budget: no rung, so no policy."""
    if cap < 1:
        return ()
    cap = round_up(cap, pad_multiple)
    rungs, rung = [], LADDER_BASE
    while rung < cap:
        rungs.append(rung)
        rung *= 2
    return (*rungs, cap)


def pad_length(rows: Sequence[Sequence[int]], length: PadLength) -> Optional[int]:
    """``pad_rows``' ``fixed_length`` for ``rows``: of a ladder, the smallest
    rung that holds the longest row. A row over the top rung (over the job's
    own length budget: the collector makes none) is not cut: the batch pads
    to its own longest row, off the ladder, as it would with no policy."""
    if not isinstance(length, (list, tuple)):
        return length
    longest = max((len(r) for r in rows), default=0)
    return next((int(rung) for rung in length if rung >= longest), None)


class PPORolloutStorage(BaseRolloutStore):
    """Replay buffer of :class:`PPORLElement` used during PPO learning."""

    def __init__(self, pad_token_id: int):
        super().__init__()
        self.pad_token_id = pad_token_id
        self.history: List[PPORLElement] = []

    def push(self, exps: List[PPORLElement]):
        self.history += exps

    def clear_history(self):
        self.history = []

    def export_history(self, location: str):
        """Append rollouts as JSON (for algorithm-distillation datasets).

        Files are named by export ordinal, not wall clock: a timestamped
        name is nondeterministic (two runs disagree byte-for-byte on the
        dataset layout) and same-second exports silently OVERWRITE each
        other — the ordinal is derived from the directory state, so every
        export lands in a fresh file and reruns produce identical names."""
        assert os.path.exists(location)
        fpath = os.path.join(location, f"epoch-{self._next_export_index(location):06d}.json")

        def exp_to_dict(exp: PPORLElement) -> dict:
            return {
                "query_tensor": np.asarray(exp.query_tensor).tolist(),
                "response_tensor": np.asarray(exp.response_tensor).tolist(),
                "logprobs": np.asarray(exp.logprobs).tolist(),
                "values": np.asarray(exp.values).tolist(),
                "rewards": np.asarray(exp.rewards).tolist(),
            }

        with open(fpath, "w") as f:
            json.dump([exp_to_dict(exp) for exp in self.history], f)

    @staticmethod
    def _next_export_index(location: str) -> int:
        """Smallest ordinal above every ``epoch-*.json`` already present
        (sorted scan: never dependent on filesystem enumeration order)."""
        taken = []
        for name in sorted(os.listdir(location)):
            if not (name.startswith("epoch-") and name.endswith(".json")):
                continue
            try:
                taken.append(int(name[len("epoch-"):-len(".json")]))
            except ValueError:
                continue  # legacy timestamped exports don't block ordinals
        return max(taken) + 1 if taken else 0

    def _pad_tokens(self, elems, pad_multiple, query_length, response_length):
        """Queries left-padded, responses right-padded, and their masks."""
        rows = [e.query_tensor for e in elems]
        queries, query_mask = pad_rows(
            rows, self.pad_token_id, "left", pad_multiple, pad_length(rows, query_length)
        )
        rows = [e.response_tensor for e in elems]
        responses, response_mask = pad_rows(
            rows, self.pad_token_id, "right", pad_multiple, pad_length(rows, response_length)
        )
        return queries, query_mask, responses, response_mask

    def collate(
        self,
        elems: List[PPORLElement],
        pad_multiple: int = 8,
        query_length: PadLength = None,
        response_length: PadLength = None,
    ) -> PPORLBatch:
        queries, query_mask, responses, response_mask = self._pad_tokens(
            elems, pad_multiple, query_length, response_length
        )
        r_len = responses.shape[1]
        logprobs, _ = pad_rows([e.logprobs for e in elems], 0.0, "right", 1, r_len, np.float32)
        values, _ = pad_rows([e.values for e in elems], 0.0, "right", 1, r_len, np.float32)
        rewards, _ = pad_rows([e.rewards for e in elems], 0.0, "right", 1, r_len, np.float32)
        # async-collection behavior logprobs ride only when EVERY element
        # carries them (mixed stores train without the IW correction)
        behavior = None
        if all(e.behavior_logprobs is not None for e in elems):
            behavior, _ = pad_rows(
                [e.behavior_logprobs for e in elems], 0.0, "right", 1, r_len, np.float32
            )
        return PPORLBatch(
            query_tensors=queries,
            response_tensors=responses,
            logprobs=logprobs,
            values=values,
            rewards=rewards,
            query_mask=query_mask,
            response_mask=response_mask,
            behavior_logprobs=behavior,
        )

    def create_loader(
        self,
        batch_size: int,
        shuffle: bool = False,
        pad_multiple: int = 8,
        query_length: PadLength = None,
        response_length: PadLength = None,
        drop_last: bool = True,
        seed: int = 0,
    ) -> BatchLoader:
        """A ladder of widths for the queries (``length_ladder``) is the pad
        policy of the PPO and GRPO learners: the shuffled rows are grouped by
        query length (``BatchLoader``'s ``group_key``) and every minibatch is
        padded to a rung, so the widths follow the rows and a run compiles at
        most one train step per pair of rungs. An int or ``None`` keeps the
        uniform partition."""
        grouped = isinstance(query_length, (list, tuple))
        return BatchLoader(
            self,
            batch_size,
            lambda elems: self.collate(elems, pad_multiple, query_length, response_length),
            shuffle=shuffle,
            drop_last=drop_last,
            seed=seed,
            group_key=(lambda e: len(e.query_tensor)) if grouped else None,
        )
