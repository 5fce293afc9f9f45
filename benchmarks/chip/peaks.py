"""The chips' published peaks, from ``peaks.json``, keyed by JAX's
``device_kind``.  A chip that is not in the table is an error."""
import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def lookup(device_kind: str) -> dict:
    with open(_PATH) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]
