"""Device time of AdamW's update in one inner step: the ops under the
program's ``update`` scope (``repro.core.diloco.make_inner_step_fn``) in
the inner step's program, over its executions."""
from benchmarks.chip.trace_reduce import scope_ms


def read(red, run):
    return scope_ms(red, run["roles"].get("inner"), "update")
