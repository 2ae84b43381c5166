"""The inner step's share of its roofline: the least time the chip could
take for one step, the larger of its model FLOPs over the peak FLOP/s and
its least HBM bytes over the peak bandwidth, over the step's device time."""
from benchmarks.chip.flops import share


def read(red, run):
    p = run["roles"].get("inner")
    if not p or not p.count:
        return None
    least = max(run["flops_per_step"] / run["peak_flops"],
                run["bytes_per_step"] / run["peak_bytes_per_s"])
    return share(least / (p.device_ns / p.count / 1e9), "inner_step_roofline")
