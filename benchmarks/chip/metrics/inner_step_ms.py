"""Device time of one inner step (``repro.core.diloco.make_inner_step``:
forward, backward and AdamW): the inner program's time in the trace over
its executions."""


def read(red, run):
    p = run["roles"].get("inner")
    return p.device_ns / p.count / 1e6 if p and p.count else None
