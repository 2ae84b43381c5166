"""Device time of the outer Nesterov step (``repro.core.diloco.
make_outer_step``) per round."""


def read(red, run):
    p = run["roles"].get("outer")
    return p.device_ns / red.program_rounds / 1e6 if p else None
