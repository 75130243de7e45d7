"""The chip's published peaks (``peaks.json``) and the byte counts the
roofline shares are charged with.

A device kind missing from the table is an error; nothing overrides the
table.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json")
    return table[device_kind]


def state_pass_bytes(num_qubits: int, num_devices: int = 1,
                     real_bytes: int = 4) -> int:
    """Bytes one pass over a device's share of a state moves: one read and
    one write of its packed (real, imaginary) planes."""
    amps = (1 << num_qubits) // num_devices
    return 2 * (2 * real_bytes * amps)
