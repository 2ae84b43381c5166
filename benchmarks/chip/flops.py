"""Operations and bytes of the programs the window drives, from shapes,
and the table of the chip's peaks (``peaks.json``, keyed by the
``device_kind`` JAX reports).

Model FLOPs per token of a training step are 6 per matmul parameter
(forward, and the two matmuls of the backward pass) plus 12·L·q_dim·S
for the attention scores and their weighted sum over the whole S×S
matrix, as the program computes it.  The embedding lookup is no matmul
and does not count; the output head does.  Recomputation under remat
does not count.
"""
from __future__ import annotations

import json
from pathlib import Path

from benchmarks.chip.reference import Arch

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


def matmul_params(a: Arch) -> int:
    d, hd = a.d_model, a.head_dim
    qd, kvd = a.num_heads * hd, a.num_kv_heads * hd
    per_layer = d * qd + 2 * d * kvd + qd * d + 3 * d * a.d_ff
    return a.num_layers * per_layer + d * a.vocab_size


def param_count(a: Arch) -> int:
    d = a.d_model
    norms = a.num_layers * (2 * d + (2 * a.head_dim if a.qk_norm else 0)) + d
    # a tied head is the embedding, already counted among the matmuls
    embed = 0 if a.tied else d * a.vocab_size
    return matmul_params(a) + norms + embed


def train_flops_per_token(a: Arch, seq_len: int) -> float:
    qd = a.num_heads * a.head_dim
    return 6.0 * matmul_params(a) + 12.0 * a.num_layers * qd * seq_len


def step_bytes(a: Arch, *, accum: bool) -> float:
    """Least HBM traffic of one inner step with bfloat16 parameters: read
    the parameters, write them back, read and write AdamW's two float32
    moments, and write the gradient (float32 when accumulated, else
    bfloat16)."""
    grad = 4 if accum else 2
    return float(param_count(a) * (2 * 2 + 4 * 4 + grad))


def share(value: float, name: str) -> float:
    """A share of a peak or roofline in percent.  Above 105% the
    operations or bytes were counted too high, or the time left out part
    of the work: refuse it rather than print it."""
    pct = 100.0 * value
    if not pct <= 105.0:
        raise ValueError(f"{name} reads {pct:.1f}% of its peak: the counted "
                         f"work or the measured time is wrong")
    return pct
