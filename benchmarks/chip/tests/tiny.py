"""Cells of the benchmark cut to a size the CPU runs in seconds: every
width shrunk by the family's ``tiny`` and listed in ``reduced``, short
sequences, the same round structure and plan, and the check's limits for
that size."""
from __future__ import annotations

from pathlib import Path

from benchmarks.chip import harness

SEQ = 32
#: the check's limits at this size, set from CPU readings of three seeds
#: (sound runs read at most loss1 1.9e-4, loss 1.7e-3, grad 3.7e-3,
#: change 0.11, stats 0.0093, outer 0.23, and over five seeds of each
#: switch cell stats_ratio 0.0036; the float8 control and every fault of
#: ``calibrate.FAULTS`` fail at least one).  A cell's own limits hold for
#: the chip's sizes, where rounding gaps are smaller.  A family may give
#: its own (``TINY_LIMITS``).
LIMITS = {"loss1": 1e-3, "loss": 5e-3, "grad": 0.05, "change": 0.3,
          "stats": 0.1, "stats_ratio": 0.02, "outer": 0.4}


def tiny_cell(name: str, root: Path = harness.ROOT) -> harness.Cell:
    cell = harness.load_cell(name, root)
    cut = cell.family.tiny(cell.config)
    config = dict(cell.config, **cut,
                  reduced=sorted(set(cut) | set(cell.config["reduced"])))
    traffic = dict(cell.traffic, seq_len=SEQ)
    limits = getattr(cell.family, "TINY_LIMITS", LIMITS)
    return cell._replace(config=config, traffic=traffic,
                         limits={k: limits[k] for k in cell.limits})
