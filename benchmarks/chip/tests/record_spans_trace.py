"""Record ``testdata/round_spans.xplane.pb`` on a TPU: the plain cell cut
to ``tiny.py``'s size, driven by the harness through its first round,
one round that starts the profiler (``bench.warm``) and two traced
rounds (``bench.round``), with the harness's spans and the program's.

    python3 benchmarks/chip/tests/record_spans_trace.py [--out PATH]

The file keeps what ``trace_reduce`` reads, and the program's spans, and
drops the rest (2 MB of compiled programs, host threads and event
stats), which needs the ``XSpace`` protobuf that TensorFlow installs;
the reduction of the kept trace equals that of the whole one, which the
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


def trim(raw: bytes) -> bytes:
    """The planes, lines and events ``trace_reduce`` reads, by name and
    time alone: each TPU plane's ``XLA Modules`` and ``XLA Ops`` lines,
    and the host plane's harness and program spans (``bench.*``,
    ``adloco.*``)."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    from benchmarks.chip import trace_reduce as T

    space = xplane_pb2.XSpace.FromString(raw)
    out = xplane_pb2.XSpace()
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
    return out.SerializeToString()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=str(HERE.parent / "testdata"
                                         / "round_spans.xplane.pb"))
    args = ap.parse_args(argv)
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    import jax

    from benchmarks.chip import harness, trace_reduce
    from benchmarks.chip.tests.tiny import tiny_cell

    harness.require_chips(jax, 1)
    prog = harness.build_and_first_round(tiny_cell(CELL), SEED, 64,
                                         harness.Norms())
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
        kept = trim(f.read())
    with open(args.out, "wb") as f:
        f.write(kept)
    red = trace_reduce.reduce_trace(args.out)
    if red != trace_reduce.reduce_trace(whole):
        raise SystemExit("record: the kept trace reduces differently")
    shutil.rmtree(log_dir, ignore_errors=True)
    print(f"record: {args.out} rounds={red.rounds} "
          f"programs={trace_reduce.top_programs(red, 5)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
