"""Record a small trace on a TPU for the reduction's tests: the plain
cell cut to ``tiny.py``'s size, driven by the harness through its first
round, one round that starts the profiler (``bench.warm``) and two
traced rounds (``bench.round``), with the harness's spans and the
program's.

    python3 benchmarks/chip/tests/record_spans_trace.py [--scopes] [--out PATH]

writes ``testdata/round_spans.xplane.pb``, or with ``--scopes``
``testdata/scopes.xplane.pb``.

The file keeps what ``trace_reduce`` reads, and the program's spans, and
drops the rest (2 MB of compiled programs, host threads and event
stats).  With ``--scopes`` it also keeps the stats of each op's metadata
that the scope reduction reads (its program and name stack).  The
reduction of the kept trace equals that of the whole one, which the
script checks.
"""
import argparse
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[2]
CELL = "qwen3-0.6b.plain-1x1"
SEED = 2 ** 33 + 12345
#: the program's own spans (``repro.core.adloco.TrainerRound``)
PROGRAM_PREFIX = "adloco."
#: stats of an op's metadata that the scope reduction reads
SCOPE_STATS = ("program_id", "tf_op")


def trim(raw: bytes, op_stats=()) -> bytes:
    """The planes, lines and events ``trace_reduce`` reads: each TPU
    plane's ``XLA Modules`` and ``XLA Ops`` lines, with the stats named
    in ``op_stats`` of each op's metadata, and the host plane's harness
    and program spans (``bench.*``, ``adloco.*``)."""
    from benchmarks.chip import trace_reduce as T
    from benchmarks.chip.xplane import messages

    XSpace = messages()["XSpace"]
    space = XSpace.FromString(raw)
    out = XSpace()
    for plane in space.planes:
        if plane.name == "/host:CPU":
            def keep(line, name):
                return name.startswith((T.SPAN_PREFIX, PROGRAM_PREFIX))
        elif T.DEVICE_PLANE.match(plane.name):
            def keep(line, name):
                return line.name in ("XLA Modules", "XLA Ops")
        else:
            continue
        kept = out.planes.add(id=plane.id, name=plane.name)
        stat_ids = {k for k, v in plane.stat_metadata.items()
                    if v.name in op_stats}
        for k in stat_ids:
            kept.stat_metadata[k].id = k
            kept.stat_metadata[k].name = plane.stat_metadata[k].name
        for line in plane.lines:
            names = plane.event_metadata
            events = [ev for ev in line.events
                      if keep(line, names[ev.metadata_id].name)]
            if not events:
                continue
            new = kept.lines.add(id=line.id, display_id=line.display_id,
                                 name=line.name,
                                 timestamp_ns=line.timestamp_ns,
                                 duration_ps=line.duration_ps)
            for ev in events:
                new.events.add(metadata_id=ev.metadata_id,
                               offset_ps=ev.offset_ps,
                               duration_ps=ev.duration_ps)
                md = plane.event_metadata[ev.metadata_id]
                kept.event_metadata[md.id].id = md.id
                kept.event_metadata[md.id].name = md.name
                if line.name == "XLA Ops" and not kept.event_metadata[
                        md.id].stats:
                    for st in md.stats:
                        if st.metadata_id in stat_ids:
                            kept.event_metadata[md.id].stats.add().CopyFrom(st)
    return out.SerializeToString()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scopes", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    out = Path(args.out or HERE.parent / "testdata" / (
        "scopes.xplane.pb" if args.scopes else "round_spans.xplane.pb"))
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    import jax

    from benchmarks.chip import harness, trace_reduce
    from benchmarks.chip.tests.tiny import tiny_cell

    harness.require_chips(jax, 1)
    # a program taken from the compilation cache keeps the name stacks of
    # the source that compiled it: compile here, so they are this one's
    jax.config.update("jax_enable_compilation_cache", False)
    cell = tiny_cell(CELL)
    prog = harness.build_and_first_round(cell, SEED, 64,
                                         harness.Norms(cell.family))
    log_dir = tempfile.mkdtemp(prefix="spans-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        harness.run_window(prog, 1e-9, 2, span="bench.warm")
        for r in (3, 4):
            harness.run_window(prog, 1e-9, r)
    finally:
        jax.profiler.stop_trace()
    whole = trace_reduce.find_xplane(log_dir)
    with open(whole, "rb") as f:
        kept = trim(f.read(), SCOPE_STATS if args.scopes else ())
    with open(out, "wb") as f:
        f.write(kept)
    red = trace_reduce.reduce_trace(str(out))
    want = trace_reduce.reduce_trace(whole)
    if not args.scopes:      # the kept trace holds no op stats to read
        red, want = (r._replace(scopes={}, innermost={}) for r in (red, want))
    if red != want:
        raise SystemExit("record: the kept trace reduces differently")
    shutil.rmtree(log_dir, ignore_errors=True)
    print(f"record: {out} rounds={red.rounds} "
          f"scopes={trace_reduce.top_scopes(red, 8)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
