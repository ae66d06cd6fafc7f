"""The table of peaks, keyed by ``device_kind``. A kind that is not in the
table is an error, never a default."""

import json
import os


def lookup(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks known for device_kind {device_kind!r}: add a row to "
            "chipbench/peaks.json with its source"
        )
    return table[device_kind]
