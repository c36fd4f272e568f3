"""Published peaks of the chips the benchmark runs on, keyed by JAX's
`device_kind`. A kind that is not in the table is an error, not a
default. No metric of the first benchmark reads a share of a peak; the
table is here for the roofline shares that come with stable kernel
names."""

import json
import os


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}: add it to benchmark/peaks.json "
                       f"with its source")
    return table[device_kind]
