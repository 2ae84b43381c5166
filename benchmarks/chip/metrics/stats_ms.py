"""Device time of the batch statistics program
(``jit(repro.core.batching.stats_from_microbatch_grads)``) per round."""


def read(red, run):
    p = run["roles"].get("stats")
    return p.device_ns / red.program_rounds / 1e6 if p else None
