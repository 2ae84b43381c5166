"""The least HBM bytes of an inner step, from the parameter count, and
the table of the chip's peaks (``peaks.json``, keyed by the
``device_kind`` JAX reports).  Each family counts its own model FLOPs
(``train_flops_per_token``) and parameters (``param_count``).
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).with_name("peaks.json")


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> dict:
    """Peak bf16 FLOP/s and HBM bytes/s of one chip of ``device_kind``."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r}; "
                            f"the table knows {sorted(table)}")
    return table[device_kind]


def step_bytes(params: int, *, accum: bool) -> float:
    """Least HBM traffic of one inner step with bfloat16 parameters: read
    the parameters, write them back, read and write AdamW's two float32
    moments, and write the gradient (float32 when accumulated, else
    bfloat16)."""
    grad = 4 if accum else 2
    return float(params * (2 * 2 + 4 * 4 + grad))


def share(value: float, name: str) -> float:
    """A share of a peak or roofline in percent.  Above 105% the
    operations or bytes were counted too high, or the time left out part
    of the work: refuse it rather than print it."""
    pct = 100.0 * value
    if not pct <= 105.0:
        raise ValueError(f"{name} reads {pct:.1f}% of its peak: the counted "
                         f"work or the measured time is wrong")
    return pct
