"""Cells of the benchmark cut to a size the CPU runs in seconds: every
width shrunk and listed in ``reduced``, short sequences, the same round
structure and plan, and the check's limits for that size."""
from __future__ import annotations

from benchmarks.chip import harness

TINY = {"hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "num_hidden_layers": 2, "vocab_size": 512}
SEQ = 32
#: the check's limits at this size, set from CPU readings of three seeds
#: (sound runs read at most loss1 1.9e-4, loss 1.7e-3, grad 3.7e-3,
#: change 0.11, stats 0.0093, outer 0.23; the float8 control and every
#: fault of ``calibrate.FAULTS`` fail at least one).  A cell's own limits
#: hold for the chip's sizes, where rounding gaps are smaller.
LIMITS = {"loss1": 1e-3, "loss": 5e-3, "grad": 0.05, "change": 0.3,
          "stats": 0.1, "outer": 0.4}


def tiny_cell(name: str) -> harness.Cell:
    cell = harness.load_cell(name)
    config = dict(cell.config)
    kv = TINY["num_key_value_heads"]
    if config["num_key_value_heads"] == config["num_attention_heads"]:
        kv = TINY["num_attention_heads"]
    config.update(TINY, num_key_value_heads=kv,
                  reduced=sorted(set(TINY) | set(config["reduced"])))
    traffic = dict(cell.traffic, seq_len=SEQ)
    return cell._replace(config=config, traffic=traffic,
                         limits={k: LIMITS[k] for k in cell.limits})
