"""The program's own spans and counts in a traced run.

``repro.obs`` opens ``transom.*`` host spans at the program's layer
boundaries and leaves a zero-length ``transom.count`` span at each count
(its stats ``counter`` and ``n``), all on the profiler's clock, which the
harness's ``bench.*`` spans and the device planes share. ``tracing.load``
keeps only the ``bench.*`` spans, so the readers here take the run's
``.xplane.pb`` once more, while the run still holds it, and keep the
program's events with the thread each ran on.

A program without ``repro.obs`` leaves no such event: ``of`` then gives
None, and every metric that reads it reads nothing. Each device op's scope
path, which the step's ``jax.named_scope`` sets, is read from the same file
by ``op_events``, through the op event's own metadata: HLO names are unique
only inside one program, so a name does not say whose op it is.
"""
from __future__ import annotations

import bisect
import glob
import os
import tempfile
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from chip import tracing

PREFIX = "transom."
COUNT = "transom.count"
# where the harness's traced run writes its profile (tempfile.mkdtemp)
TRACE_GLOB = ("chip_bench_trace_*", "plugins", "profile", "*", "*.xplane.pb")


@dataclass
class Span:
    name: str
    start: float
    end: float
    thread: int
    meta: Dict[str, object] = field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def holds(self, other: "Span") -> bool:
        """``other`` ran on this span's thread, inside it."""
        return (other.thread == self.thread and self.start <= other.start
                and other.end <= self.end)


@dataclass
class Program:
    spans: List[Span] = field(default_factory=list)     # counts left out
    counts: List[Span] = field(default_factory=list)    # transom.count
    window: Optional[tracing.Interval] = None           # bench.window
    path: Optional[str] = None                          # the file read
    ops: Optional[Dict[str, List[tracing.Event]]] = None  # op_events(path)

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def inside(self, name: str, parents: Sequence[Span]) -> List[Span]:
        """Spans ``name`` that some span of ``parents`` holds."""
        return [s for s in self.named(name)
                if any(p.holds(s) for p in parents)]

    def seconds(self, name: str, parents: Optional[Sequence[Span]] = None
                ) -> float:
        spans = self.named(name) if parents is None \
            else self.inside(name, parents)
        return sum(s.seconds for s in spans)

    def counted(self, counter: str,
                parents: Optional[Sequence[Span]] = None) -> float:
        """Sum of the counts of ``counter``, held by ``parents`` if given."""
        return sum(float(c.meta["n"]) for c in self.counts
                   if c.meta.get("counter") == counter
                   and (parents is None or any(p.holds(c) for p in parents)))


def load(path: str) -> Program:
    """The ``transom.*`` events of a trace, and its ``bench.window``.
    Threads are numbered by their line in the file."""
    from jax.profiler import ProfileData

    prog = Program(path=path)
    thread = 0
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            thread += 1
            for e in line.events:
                name = e.name
                if name == "bench.window" and prog.window is None:
                    s = e.start_ns * 1e-9      # as tracing.load reads it
                    prog.window = (s, s + e.duration_ns * 1e-9)
                if not name.startswith(PREFIX):
                    continue
                s = e.start_ns * 1e-9
                span = Span(name, s, s + e.duration_ns * 1e-9, thread,
                            dict(e.stats))
                (prog.counts if name == COUNT else prog.spans).append(span)
    prog.spans.sort(key=lambda x: x.start)
    prog.counts.sort(key=lambda x: x.start)
    return prog


def trace_files() -> List[str]:
    """Profiles of traced runs under the temp directory, newest first."""
    paths = glob.glob(os.path.join(tempfile.gettempdir(), *TRACE_GLOB))
    return sorted(paths, key=os.path.getmtime, reverse=True)


def located(run: dict) -> Optional[Program]:
    """The run's trace, read for the program: the profile under the temp
    directory whose ``bench.window`` is the run's, or None. Read once per
    run and kept in the run record."""
    if "program_trace" not in run:
        run["program_trace"] = _find(run)
    return run["program_trace"]


def of(run: dict) -> Optional[Program]:
    """The program's events in the run's trace, or None where the program
    left none."""
    prog = located(run)
    return prog if prog is not None and (prog.spans or prog.counts) else None


def _find(run: dict) -> Optional[Program]:
    tr = run.get("trace")
    win = tr.span("bench.window") if tr is not None else None
    if win is None:
        return None
    for path in trace_files():
        prog = load(path)
        if prog.window == win:
            return prog
    return None


# --------------------------------------------------------------------------- #
# Scope paths of the device ops
# --------------------------------------------------------------------------- #
def _xspace():
    """A message class for the part of the profiler's ``XSpace`` that
    holds the device planes' events and their metadata
    (tsl/profiler/protobuf/xplane.proto, its field numbers); every other
    field is skipped."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    f = descriptor_pb2.FileDescriptorProto(name="chip_xspace.proto",
                                           package="chip_xspace")

    def msg(name, *fields):
        m = f.message_type.add(name=name)
        for fname, number, label, kind in fields:
            if isinstance(kind, str):               # a message of this file
                m.field.add(name=fname, number=number, label=label,
                            type=F.TYPE_MESSAGE,
                            type_name=f".chip_xspace.{kind}")
            else:
                m.field.add(name=fname, number=number, label=label,
                            type=kind)

    msg("XStat", ("metadata_id", 1, one, F.TYPE_INT64),
        ("str_value", 5, one, F.TYPE_STRING),
        ("ref_value", 7, one, F.TYPE_UINT64))
    msg("XEvent", ("metadata_id", 1, one, F.TYPE_INT64),
        ("offset_ps", 2, one, F.TYPE_INT64),
        ("duration_ps", 3, one, F.TYPE_INT64))
    msg("XLine", ("name", 2, one, F.TYPE_STRING),
        ("timestamp_ns", 3, one, F.TYPE_INT64),
        ("events", 4, many, "XEvent"))
    msg("XEventMetadata", ("name", 2, one, F.TYPE_STRING),
        ("stats", 5, many, "XStat"))
    msg("XStatMetadata", ("name", 2, one, F.TYPE_STRING))
    # a proto map is, on the wire, a repeated (key, value) message
    msg("EventMetadataEntry", ("key", 1, one, F.TYPE_INT64),
        ("value", 2, one, "XEventMetadata"))
    msg("StatMetadataEntry", ("key", 1, one, F.TYPE_INT64),
        ("value", 2, one, "XStatMetadata"))
    msg("XPlane", ("name", 2, one, F.TYPE_STRING),
        ("lines", 3, many, "XLine"),
        ("event_metadata", 4, many, "EventMetadataEntry"),
        ("stat_metadata", 5, many, "StatMetadataEntry"))
    msg("XSpace", ("planes", 1, many, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chip_xspace.XSpace"))


def op_events(path: str, plane=tracing.TPU_PLANE, op_line: str = "XLA Ops"
              ) -> Dict[str, List[tracing.Event]]:
    """Each device plane's ops as ``tracing.load`` keeps them, with each
    op's scope path in place of its name: the ``tf_op`` stat of the
    event's own metadata, the jit, transform and ``jax.named_scope`` path
    of the op (``jit(core)/transpose(jvp(loss))/...``), "" where it has
    none. ``ProfileData`` gives neither an event's metadata nor the stats
    of that metadata, so they are read from the file here."""
    space = _xspace()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    out: Dict[str, List[tracing.Event]] = {}
    for p in space.planes:
        if not plane.match(p.name):
            continue
        stat_names = {e.key: e.value.name for e in p.stat_metadata}
        names, paths = {}, {}
        for entry in p.event_metadata:
            names[entry.key] = entry.value.name
            for st in entry.value.stats:
                if stat_names.get(st.metadata_id) == "tf_op":
                    paths[entry.key] = (st.str_value or stat_names.get(
                        st.ref_value, ""))
        for line in p.lines:
            if not line.name.startswith(op_line):
                continue
            for e in line.events:
                # whole ns, as ProfileData gives them to tracing.load
                name = names.get(e.metadata_id, "")
                d = e.duration_ps // 1000
                if d <= 0 or "::" in name or name.startswith("end: "):
                    continue
                s = (line.timestamp_ns + e.offset_ps // 1000) * 1e-9
                out.setdefault(p.name, []).append(
                    (paths.get(e.metadata_id, ""), s, s + d * 1e-9))
    for evs in out.values():
        evs.sort(key=lambda x: x[1])
    return out


def ops(run: dict) -> Optional[Dict[str, List[tracing.Event]]]:
    """``op_events`` of the run's trace, read once."""
    prog = located(run)
    if prog is None:
        return None
    if prog.ops is None and prog.path is not None:
        prog.ops = op_events(prog.path)
    return prog.ops or None


# --------------------------------------------------------------------------- #
# What several readers share
# --------------------------------------------------------------------------- #
def mean_seconds(run: dict, name: str) -> Optional[float]:
    """Mean duration of the spans ``name`` in the window."""
    prog = of(run)
    spans = prog.named(name) if prog else []
    return sum(s.seconds for s in spans) / len(spans) if spans else None


def per_resume_count(run: dict, counter: str) -> Optional[float]:
    """The window's counts of ``counter``, per resume."""
    prog = of(run)
    if prog is None or not run["resumes"]:
        return None
    return prog.counted(counter) / len(run["resumes"])


def bandwidth(run: dict, counter: str, name: str,
              parent: Optional[str] = None) -> Optional[float]:
    """Bytes counted by ``counter`` over the seconds of the spans ``name``,
    both held by the spans ``parent`` if given, in GB/s."""
    prog = of(run)
    parents = prog.named(parent) if prog and parent else None
    if prog is None or parents == []:
        return None
    secs = prog.seconds(name, parents)
    return prog.counted(counter, parents) / secs * 1e-9 if secs else None


def persists(prog: Optional[Program]) -> Tuple[List[Span], int]:
    """The window's ``transom.persist`` spans (one per step and rank, the
    spans still open when the trace stopped are not in it) and the number
    of steps they persist."""
    spans = prog.named("transom.persist") if prog else []
    return spans, len({s.meta.get("step") for s in spans})


def per_persist(run: dict, names: Iterable[str]) -> Optional[float]:
    """Seconds of the spans ``names`` inside the window's persists, per
    step persisted."""
    prog = of(run)
    spans, steps = persists(prog)
    if not steps:
        return None
    return sum(prog.seconds(n, spans) for n in names) / steps


def per_restore(run: dict, names: Iterable[str],
                inner: bool = True) -> Optional[float]:
    """Seconds of the spans ``names`` per ``transom.restore`` in the
    window: inside the restores, or anywhere in the window (``inner``
    False, for the work after the engine returns)."""
    prog = of(run)
    restores = prog.named("transom.restore") if prog else []
    if not restores:
        return None
    return sum(prog.seconds(n, restores if inner else None)
               for n in names) / len(restores)


def _holding(intervals: Sequence[tracing.Interval], t: float) -> bool:
    """Whether one of the sorted, disjoint ``intervals`` holds ``t``."""
    i = bisect.bisect_right(intervals, (t, float("inf"))) - 1
    return i >= 0 and t < intervals[i][1]


def scoped_ms(run: dict, match) -> Optional[float]:
    """Device self time of the ops whose scope path ``match``es, inside
    the window's training steps (``tracing.step_intervals``), per step,
    mean over chips, in ms; None where no op's path matches, or where the
    program left no events of its own (a program without the step's named
    scopes). Raises where it left some but no op in a step carries the
    ``loss`` scope: the step ran from an executable compiled without it."""
    tr = run["trace"]
    devices = ops(run) if of(run) and tr is not None else None
    if not devices or not run["steps"]:
        return None
    steps = tracing.union(tracing.step_intervals(tr))
    tot, found, seen, scoped = 0.0, False, False, False
    for evs in devices.values():
        for path, s, _, own in tracing.self_times(evs):
            if not path:
                continue
            in_step = _holding(steps, s)
            seen = seen or in_step
            scoped = scoped or (in_step and "loss" in path)
            if not match(path):
                continue
            found = True
            if in_step:
                tot += own
    if seen and not scoped:
        raise RuntimeError("no op of the window's steps carries the 'loss' "
                           "scope: the step's executable lacks its named "
                           "scopes")
    if not found:
        return None
    return 1e3 * tot / len(devices) / len(run["steps"])
