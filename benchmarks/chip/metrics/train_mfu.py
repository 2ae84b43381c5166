"""Model FLOP/s utilization of the whole round: model FLOPs of every
inner step traced over the traced window times the chip's peak."""
from benchmarks.chip.flops import share


def read(red, run):
    if red.window_ns <= 0 or not run["steps_traced"]:
        return None
    flops = run["flops_per_step"] * run["steps_traced"]
    return share(flops / (red.window_ns / 1e9 * run["peak_flops"]),
                 "train_mfu")
