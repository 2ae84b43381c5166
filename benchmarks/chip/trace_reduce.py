"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the
per-layer metrics read.

On a TPU the trace has one plane per chip (``/device:TPU:<n>``) with a
line ``XLA Modules`` (one event per execution of a compiled program,
named ``<module>(<program id>)``) and a line ``XLA Ops`` (one event per
operation, nested ops included), and a host plane ``/host:CPU`` whose
thread lines carry the harness's ``TraceAnnotation`` spans
(``bench.round``, ``bench.inner``, ``bench.outer``, ``bench.data``).
Device and host events share one clock, in nanoseconds from the start
of the trace.

* busy: the union of the intervals of all ops on the device inside the
  traced window (first ``bench.round`` start to last one's end),
  averaged over the chips in the trace;
* programs: device time and executions of each compiled program in the
  whole trace, keyed by its name and program id, so two programs of one
  name stay apart.  The trace holds whole rounds only: the measured
  ``bench.round`` spans and the ``bench.warm`` round before them, which
  ran after the profiler started.  Programs are counted over the trace,
  not clipped to the window, because the device's clock in the trace
  can lead the host's by a millisecond or so, which would cut a round's
  first program off at the window's edge;
* idle gaps: the stretches of the traced window in which no op ran, each
  attributed to the innermost harness span that covers its midpoint;
* scopes: device time of each program's operations by scope, over the
  same whole trace as programs.  An op's named scopes come from its name
  stack, the ``tf_op`` stat of its event metadata on the device plane
  (XLA's ``op_name``: ``jit(f)/grad/transpose(jvp(attention))/dot_general``),
  and the program it ran in from the ``program_id`` stat there.  Only
  ops that contain no other op (not a ``while`` around its body) are
  summed, and an op counts once under each scope on its stack.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_NAME = re.compile(r"^(?P<name>.*)\((?P<id>-?\d+)\)$")
SPAN_PREFIX = "bench."


class Program(NamedTuple):
    name: str
    program_id: str
    count: int
    device_ns: float


class Reduction(NamedTuple):
    window_ns: float            # first bench.round start to last one's end
    busy_ns: float              # union of device ops inside the window
    chips: int
    rounds: int                 # bench.round spans in the trace
    program_rounds: int         # whole rounds the programs were counted over
    programs: Dict[Tuple[str, str], Program]
    gaps: List[Tuple[str, float]]   # (covering span, ns), longest first
    #: (program name, program id, scope) -> device ns of the leaf ops
    #: under that scope, every execution in the trace
    scopes: Dict[Tuple[str, str, str], float] = {}
    #: (program name, program id, innermost scope or "") -> device ns
    innermost: Dict[Tuple[str, str, str], float] = {}

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce_trace(path: str, *, span_prefix: str = SPAN_PREFIX) -> Reduction:
    """Reduce the trace at ``path`` (an ``.xplane.pb`` file)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans: List[Tuple[float, float, str]] = []
    device_ops: List[List[Tuple[float, float]]] = []
    modules: List[Tuple[Tuple[str, str], float]] = []
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(span_prefix):
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
        elif DEVICE_PLANE.match(plane.name):
            ops: List[Tuple[float, float]] = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((ev.start_ns, ev.start_ns + ev.duration_ns)
                               for ev in line.events)
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        m = MODULE_NAME.match(ev.name)
                        key = ((m["name"], m["id"]) if m
                               else (ev.name, ""))
                        modules.append((key, ev.duration_ns))
            device_ops.append(ops)
    rounds = [s for s in spans if s[2] == span_prefix + "round"]
    if not rounds:
        raise ValueError(f"{path}: no {span_prefix}round span in the trace")
    if not device_ops:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    lo = min(s for s, _, _ in rounds)
    hi = max(e for _, e, _ in rounds)
    programs: Dict[Tuple[str, str], List[float]] = defaultdict(
        lambda: [0, 0.0])
    for key, dur in modules:
        programs[key][0] += 1
        programs[key][1] += dur
    busy_per_chip, gaps = [], []
    for ops in device_ops:
        busy = _clip(_union(ops), lo, hi)
        busy_per_chip.append(sum(e - s for s, e in busy))
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((_covering(spans, (s + e) / 2), e - s))
    gaps.sort(key=lambda g: -g[1])
    scopes: Dict[Tuple[str, str, str], float] = defaultdict(float)
    innermost: Dict[Tuple[str, str, str], float] = defaultdict(float)
    names = {k[1]: k[0] for k in programs}
    for op in leaf_ops(path):
        name = names.get(op.program_id, "")
        for scope in op.scopes:
            scopes[(name, op.program_id, scope)] += op.ns
        inner = op.scopes[-1] if op.scopes else ""
        innermost[(name, op.program_id, inner)] += op.ns
    return Reduction(
        window_ns=hi - lo,
        busy_ns=sum(busy_per_chip) / len(busy_per_chip),
        chips=len(device_ops), rounds=len(rounds),
        program_rounds=sum(1 for s in spans
                           if s[2] in (span_prefix + "round",
                                       span_prefix + "warm")),
        programs={k: Program(k[0], k[1], int(c), float(ns))
                  for k, (c, ns) in programs.items()},
        gaps=gaps, scopes=dict(scopes), innermost=dict(innermost))


# ---------------------------------------------------------------- scopes
#: segments of a name stack that wrap code and name no scope of the user
WRAPPERS = frozenset({"while", "body", "cond", "closed_call", "core_call",
                      "checkpoint", "remat", "rematted_computation", "scan",
                      "pjit", "jit", "custom_jvp_call", "custom_vjp_call",
                      "shard_map", "xla_call", "xla_pmap"})
#: transforms whose parentheses hold the scopes they were applied to
TRANSFORMS = frozenset({"jvp", "transpose", "vmap", "batching", "vjp",
                        "linearize", "remat", "checkpoint"})
SCOPE = re.compile(r"^[A-Za-z_][A-Za-z0-9_.-]*$")
BRANCH = re.compile(r"^branch_\d+")


def _split(stack: str) -> List[str]:
    """``stack`` split at each ``/`` outside parentheses."""
    out, depth, start = [], 0, 0
    for i, ch in enumerate(stack):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            out.append(stack[start:i])
            start = i + 1
    out.append(stack[start:])
    return [seg for seg in out if seg]


def _scopes_in(segments: Iterable[str]) -> List[str]:
    out: List[str] = []
    for seg in segments:
        head, paren, inner = seg.partition("(")
        if paren:
            if head in TRANSFORMS and inner.endswith(")"):
                out.extend(_scopes_in(_split(inner[:-1])))
        elif (SCOPE.match(seg) and seg not in WRAPPERS
              and not BRANCH.match(seg)):
            out.append(seg)
    return out


def named_scopes(op_name: str) -> Tuple[str, ...]:
    """The user's named scopes on an op's name stack, outermost first,
    each once: ``jit(f)/grad/transpose(jvp(attention))/while/body/dot``
    gives ``("grad", "attention")``.  The last segment, the operation
    itself, and the ``:type`` the profiler appends are no scope."""
    stack = op_name.rpartition(":")[0] if ":" in op_name else op_name
    segments = _split(stack)[:-1]
    return tuple(dict.fromkeys(_scopes_in(segments)))


class Op(NamedTuple):
    program_id: str
    ns: float                   # every execution in the trace together
    scopes: Tuple[str, ...]     # named, outermost first


def leaf_ops(path: str) -> List[Op]:
    """Device time of the ops of each TPU plane that contain no other op,
    one entry for each op's metadata (every execution of that op in the
    trace), with its program and named scopes."""
    from benchmarks.chip.xplane import read_space, stat_value

    ops: Dict[Tuple[int, int], float] = defaultdict(float)
    meta: Dict[Tuple[int, int], Tuple[str, str]] = {}
    for i, plane in enumerate(read_space(path).planes):
        if not DEVICE_PLANE.match(plane.name):
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            t0 = line.timestamp_ns * 1000
            evs = sorted(((t0 + ev.offset_ps, t0 + ev.offset_ps
                           + ev.duration_ps, ev.metadata_id)
                          for ev in line.events),
                         key=lambda e: (e[0], -e[1]))
            for s, e, mid in _leaves(evs):
                ops[(i, mid)] += (e - s) / 1e3
                if (i, mid) not in meta:
                    stats = {stat_names.get(st.metadata_id):
                             stat_value(st, stat_names)
                             for st in plane.event_metadata[mid].stats}
                    meta[(i, mid)] = (str(stats.get("program_id", "")),
                                      stats.get("tf_op") or "")
    return [Op(meta[key][0], ns, named_scopes(meta[key][1]))
            for key, ns in ops.items()]


def _leaves(evs):
    """The events, sorted by start (and longest first), that hold no
    other event of the list inside their span."""
    holds = [False] * len(evs)
    open_: List[int] = []
    for i, (s, e, _) in enumerate(evs):
        while open_ and evs[open_[-1]][1] <= s:
            open_.pop()
        if open_ and e <= evs[open_[-1]][1] and (s, e) != evs[open_[-1]][:2]:
            holds[open_[-1]] = True
        open_.append(i)
    return [ev for ev, h in zip(evs, holds) if not h]


def scope_ms(red: Reduction, program, scope: str) -> Optional[float]:
    """Device ms under ``scope`` in one execution of ``program`` (a
    ``Program``), or None where the trace holds none."""
    if program is None or not program.count:
        return None
    ns = red.scopes.get((program.name, program.program_id, scope))
    return ns / program.count / 1e6 if ns else None


def _covering(spans, t: float) -> str:
    """Name of the shortest harness span that covers time ``t``."""
    best: Optional[Tuple[float, str]] = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "outside"


def assign_roles(red: Reduction, expected: Dict[str, Tuple[str, int]]
                 ) -> Dict[str, Program]:
    """Map each role to its program.  ``expected`` gives, per role, the
    program's module name and how many times it runs in one round, both
    recorded by the harness at warm-up.  Programs of one name are told
    apart by their program id and, where several roles share the name,
    by executions per traced round, never by duration.  A role that no
    single program matches is left out."""
    roles: Dict[str, Program] = {}
    for role, (name, per_round) in expected.items():
        same_name = [p for p in red.programs.values() if p.name == name]
        shared = sum(1 for n, _ in expected.values() if n == name) > 1
        if shared:
            same_name = [p for p in same_name
                         if p.count == per_round * red.program_rounds]
            if len({r for r, (n, c) in expected.items()
                    if n == name and c == per_round}) > 1:
                continue
        if len(same_name) == 1:
            roles[role] = same_name[0]
    return roles


def top_scopes(red: Reduction, n: int = 10) -> List[List]:
    """The ``n`` largest (program, innermost scope) entries, in seconds
    over the trace; ``-`` where an op is under no scope."""
    top = sorted(red.innermost.items(), key=lambda kv: -kv[1])[:n]
    return [[f"{name}({pid}) {scope or '-'}", ns / 1e9]
            for (name, pid, scope), ns in top]


def top_gaps(red: Reduction, n: int = 10) -> List[List]:
    return [[name, ns / 1e9] for name, ns in red.gaps[:n]]
