"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/bench.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for.  Exits non-zero, and prints no result, off the TPU.  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last ``check``, each compared number beside its limit.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the checkout's root, for ``benchmarks.chip``, and its ``src``, for
    # the program; not this file's own directory
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    # the TPU runtime's logs go under this run's TMPDIR, not a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    from benchmarks.chip import harness
    return harness.main(args.workload, args.seed, args.seconds,
                        bool(args.trace), T_PROCESS)


if __name__ == "__main__":
    sys.exit(main())
