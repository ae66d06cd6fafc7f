"""GRPO rollout storage: the PPO replay-buffer/collator shape
(``trlx/pipeline/ppo_pipeline.py:13-80`` analogue) carrying per-sequence
advantages and reference logprobs instead of values/per-token rewards."""

from typing import List

import numpy as np

from trlx_tpu.data.grpo_types import GRPORLBatch, GRPORLElement
from trlx_tpu.pipeline.offline_pipeline import pad_rows
from trlx_tpu.pipeline.ppo_pipeline import PadLength, PPORolloutStorage


class GRPORolloutStorage(PPORolloutStorage):
    """Replay buffer of :class:`GRPORLElement` used during GRPO learning.

    Shares the PPO store's push/clear/loader machinery; only the element
    fields differ (per-sequence advantage + reference logprobs instead of
    values/per-token rewards), so only collation and export change."""

    def export_history(self, location: str):
        """Append rollouts as JSON (reference ``ppo_pipeline.py:30-40``);
        ordinal file naming shared with the PPO store — deterministic and
        collision-free where the old timestamp name was neither."""
        import json
        import os

        assert os.path.exists(location)
        fpath = os.path.join(location, f"epoch-{self._next_export_index(location):06d}.json")
        with open(fpath, "w") as f:
            json.dump(
                [
                    {
                        "query_tensor": np.asarray(e.query_tensor).tolist(),
                        "response_tensor": np.asarray(e.response_tensor).tolist(),
                        "logprobs": np.asarray(e.logprobs).tolist(),
                        "ref_logprobs": np.asarray(e.ref_logprobs).tolist(),
                        "advantage": float(e.advantage),
                    }
                    for e in self.history
                ],
                f,
            )

    def collate(
        self,
        elems: List[GRPORLElement],
        pad_multiple: int = 8,
        query_length: PadLength = None,
        response_length: PadLength = None,
    ) -> GRPORLBatch:
        queries, query_mask, responses, response_mask = self._pad_tokens(
            elems, pad_multiple, query_length, response_length
        )
        r_len = responses.shape[1]
        logprobs, _ = pad_rows([e.logprobs for e in elems], 0.0, "right", 1, r_len, np.float32)
        ref_logprobs, _ = pad_rows([e.ref_logprobs for e in elems], 0.0, "right", 1, r_len, np.float32)
        behavior = None
        if all(e.behavior_logprobs is not None for e in elems):
            behavior, _ = pad_rows(
                [e.behavior_logprobs for e in elems], 0.0, "right", 1, r_len, np.float32
            )
        return GRPORLBatch(
            query_tensors=queries,
            response_tensors=responses,
            logprobs=logprobs,
            ref_logprobs=ref_logprobs,
            advantages=np.asarray([e.advantage for e in elems], np.float32),
            query_mask=query_mask,
            response_mask=response_mask,
            behavior_logprobs=behavior,
        )
