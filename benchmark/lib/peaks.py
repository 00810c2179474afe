"""Published peaks of one chip, keyed by the exact ``device_kind`` string
JAX reports. A device that is not in ``peaks.json`` has no peak: asking
for one is an error, never a default."""

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(_PATH) as fh:
        table = json.load(fh)
    try:
        return table[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}: add it "
            f"to benchmark/lib/peaks.json with its source") from None
