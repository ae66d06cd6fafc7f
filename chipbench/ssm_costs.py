"""Operations and bytes of the Mamba-2 recurrence, from shapes.

``flops.py`` reads the scan's and the conv's operations into every count of
a step's required work (``learn_mfu_pct``, since PR 39); the bytes and the
decode step's costs wait for a kernel of ``ops/ssd.py`` and its roofline
share (``layers.py::trace_op_roofline``). ``rows`` sequences, ``heads`` heads of
``head_dim`` channels, ``state`` the state size, ``groups`` groups of B and
C. Required work only: what the recurrence needs, nothing recomputed.

    python3 -m chipbench.ssm_costs        # the new cell's shapes against the v5e's peaks
"""

from typing import Dict

STATE_BYTES = 4  # the recurrent state is float32 whatever the compute dtype


def step_costs(rows: int, heads: int, head_dim: int, state: int, groups: int,
               act_bytes: int = 2) -> Dict[str, float]:
    """One decode token of one block (``ops/ssd.py::ssd_step``): the state is
    read once and written once; decay, rank-one update and read-out are 2 + 2
    + 2 operations an element of the state (multiply by the decay, multiply
    and add the outer product, multiply and add into ``y``)."""
    elements = rows * heads * head_dim * state
    vectors = rows * (2 * heads * head_dim + 2 * groups * state + heads)  # x, y, B, C, dt
    return {
        "flops": 6.0 * elements,
        "bytes": 2.0 * STATE_BYTES * elements + act_bytes * vectors,
        "state_bytes": float(STATE_BYTES * elements),
    }


def scan_costs(rows: int, tokens: int, heads: int, head_dim: int, state: int, groups: int,
               chunk: int = 128, act_bytes: int = 2) -> Dict[str, float]:
    """``tokens`` positions of one block through the chunked scan
    (``ops/ssd.py::ssd_chunked``), forward only: per chunk of ``Q`` the
    ``C B^T`` scores a group (``2 Q^2 N``), their product with ``x`` a head
    (``2 Q^2 P`` over the causal half counted whole, as the program computes
    it), the chunk's state (``2 Q P N`` a head) and the carried state's part
    of the output (``2 Q P N`` a head). Bytes: x, y, B, C, dt once, and the
    float32 state at every chunk boundary written and read."""
    q = min(chunk, tokens)
    chunks = -(-tokens // q)
    per_chunk = (
        groups * 2.0 * q * q * state
        + heads * 2.0 * q * q * head_dim
        + heads * 4.0 * q * head_dim * state
    )
    vectors = rows * tokens * (2 * heads * head_dim + 2 * groups * state + heads)
    boundary = rows * chunks * heads * head_dim * state
    return {
        "flops": rows * chunks * per_chunk,
        "bytes": act_bytes * vectors + 2.0 * STATE_BYTES * boundary,
        "state_bytes": float(STATE_BYTES * rows * heads * head_dim * state),
    }


def conv_costs(rows: int, tokens: int, channels: int, width: int, act_bytes: int = 2
               ) -> Dict[str, float]:
    """The causal depthwise conv over x, B and C, forward: a multiply and an
    add a tap, ``width`` taps a channel a token; each value read and written
    once."""
    elements = float(rows) * tokens * channels
    return {"flops": 2.0 * width * elements, "bytes": 2.0 * act_bytes * elements}


if __name__ == "__main__":
    import json

    from chipbench import job, peaks

    dims = job.load_config("falcon-h1-34b-l4")["published"]
    shape = (dims["mamba_n_heads"], dims["mamba_d_head"], dims["mamba_d_state"], dims["mamba_n_groups"])
    peak = peaks.lookup("TPU v5 lite")
    for name, costs in (("step, 64 rows", step_costs(64, *shape)),
                        ("scan, 64 x 640", scan_costs(64, 640, *shape, chunk=dims["mamba_chunk_size"]))):
        floor_ms = 1e3 * max(costs["bytes"] / peak["hbm_bytes_per_s"],
                             costs["flops"] / peak["bf16_flops_per_s"])
        print(json.dumps({"what": name, "a_block": costs, "floor_ms_a_block": floor_ms}))
