"""The serving program's own spans and its attention scope, from a trace.

The program records host spans around the stretches of a scheduler
round (``serve.*`` inside ``SlotServeEngine.step``, ``frontend.*`` in
the front-end's loop) and runs its paged attention read path under one
name scope. This module reads both from the ``.xplane.pb`` that
``jax.profiler`` wrote, beside the benchmark's own spans, and reduces
them to what the per-layer metrics of the round's inside read:

* :func:`extract` keeps the device ops and modules as
  :func:`benchlib.trace.extract` does, each op with a flag saying
  whether it ran inside :data:`SCOPE`, and the host events named in
  :data:`PROGRAM_SPANS` or ``trace.HOST_SPANS``;
* :func:`reduce` gives, for each traced ``serve.step``, its time and
  the time its ``serve.wait`` and ``serve.plan`` took; the gaps
  between consecutive steps; the device self time of the scoped ops;
  the device's idle time split over the innermost span covering it;
  and the longest idle gaps named likewise.

Program spans win over the benchmark's where both cover a moment; the
benchmark's are the fallback. A program without these spans (one that
predates them) yields empty lists and zeros, and the readers built on
them return None. Times are nanoseconds on the trace's one clock.
"""

from __future__ import annotations

from typing import Any, Dict, List, Set, Tuple

from . import trace

#: Host spans of the serving program (``repro.serve.engine.SPANS``),
#: named here so that the benchmark imports nothing of the program.
PROGRAM_SPANS = ("serve.step", "serve.admit", "serve.plan", "serve.grant",
                 "serve.grow", "serve.dispatch", "serve.wait",
                 "serve.harvest", "serve.retire", "frontend.pump",
                 "frontend.intake")
STEP, WAIT, PLAN = "serve.step", "serve.wait", "serve.plan"
#: Name scope of the paged attention read path
#: (``repro.models.attention.PAGED_ATTENTION_SCOPE``).
SCOPE = "paged_attention"
#: Metadata stat of a device op that holds its scope path.
SCOPE_STAT = "tf_op"

Op = Tuple[str, int, int, bool]         # (name, start_ns, duration_ns, scoped)


def in_scope(path: str) -> bool:
    """Whether a scope path (``jit(f)/paged_attention/dot_general``)
    has :data:`SCOPE` as one of its parts."""
    return SCOPE in path.split("/")


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of one protobuf message:
    an int for a varint, a memoryview for the other wire types."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} in a trace")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def scoped_names(path: str) -> Set[str]:
    """Names of the device ops whose scope path runs through
    :data:`SCOPE`. A v5e trace keeps an op's path (its HLO metadata
    ``op_name``) in the :data:`SCOPE_STAT` stat of the op's event
    *metadata*, which ``ProfileData`` does not expose; so the device
    plane's metadata is read from the ``.xplane.pb`` itself (an XSpace:
    planes 1; a plane's name 2, event metadata 4 and stat metadata 5,
    both maps of id 1 to message 2; a metadata's name 2 and stats 5; a
    stat's metadata id 1, string 5 or reference to a stat metadata's
    name 7)."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out: Set[str] = set()
    for field, plane in _fields(data):
        if field != 1:
            continue
        parts = list(_fields(plane))
        name = next((_text(v) for k, v in parts if k == 2), "")
        if not trace.DEVICE_PLANE.match(name):
            continue
        stat_name, events = {}, []
        for k, entry in parts:
            if k in (4, 5):
                e = dict(_fields(entry))
                md = list(_fields(e.get(2, b"")))
                if k == 5:
                    stat_name[e.get(1, 0)] = next(
                        (_text(v) for j, v in md if j == 2), "")
                else:
                    events.append(md)
        for md in events:
            op = next((_text(v) for j, v in md if j == 2), "")
            for j, st in md:
                if j != 5:
                    continue
                st = dict(_fields(st))
                if stat_name.get(st.get(1, 0)) != SCOPE_STAT:
                    continue
                path_ = (_text(st[5]) if 5 in st
                         else stat_name.get(st.get(7, -1), ""))
                if in_scope(path_):
                    out.add(op)
    return out


def extract(path: str) -> Dict[str, List[Any]]:
    """Device ops (with their scope flag), modules and host spans."""
    from jax.profiler import ProfileData
    scoped = scoped_names(path)
    pd = ProfileData.from_file(path)
    keep = set(PROGRAM_SPANS) | set(trace.HOST_SPANS) | {"window"}
    out: Dict[str, List[Any]] = {"ops": [], "modules": [], "host": []}
    for plane in pd.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    out["ops"] += [(e.name, int(e.start_ns),
                                    int(e.duration_ns), e.name in scoped)
                                   for e in line.events]
                elif line.name == trace.MODULES_LINE:
                    out["modules"] += [(e.name, int(e.start_ns),
                                        int(e.duration_ns))
                                       for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out["host"] += [(e.name, int(e.start_ns), int(e.duration_ns))
                                for e in line.events if e.name in keep]
    return out


def _window(ev) -> Tuple[int, int]:
    win = [e for e in ev["host"] if e[0] == "window"]
    return (win[0][1], win[0][1] + win[0][2]) if win else (0, 0)


def _inside(events, lo: int, hi: int):
    return [e for e in events if e[1] >= lo and e[1] + e[2] <= hi]


def _scoped_self_ns(ops: List[Op], lo: int, hi: int) -> int:
    """Device self time (an op's time less the ops nested in it, as
    ``trace`` counts it) of the scoped ops that start in the window."""
    inside = [(str(i), s, min(d, hi - s)) for i, (_, s, d, _) in
              enumerate(ops) if lo <= s < hi]
    return sum(t for i, t in trace._self_times(inside) if ops[int(i)][3])


def _idle(ev, lo: int, hi: int) -> List[Tuple[int, int]]:
    busy = trace._union(trace._clip(
        [e[:3] for e in ev["ops"]] or ev["modules"], lo, hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def _split_idle(idle, spans) -> Dict[str, int]:
    """Idle nanoseconds per innermost covering span: a program span if
    one covers the moment, else a benchmark span, else ``none``; the
    shortest covering span is the innermost."""
    points = []
    for i, (n, s, d) in enumerate(spans):
        points += [(s, 1, i), (s + d, -1, i)]
    for a, b in idle:
        points += [(a, 2, -1), (b, -2, -1)]
    points.sort(key=lambda p: (p[0], p[1]))
    prog = set(PROGRAM_SPANS)
    active: Dict[int, Tuple[bool, int, str]] = {}
    out: Dict[str, int] = {}
    idle_on, prev = False, None
    for t, kind, i in points:
        if idle_on and prev is not None and t > prev:
            best = min(active.values(), default=None,
                       key=lambda a: (not a[0], a[1]))
            name = best[2] if best else "none"
            out[name] = out.get(name, 0) + t - prev
        prev = t
        if kind == 1:
            n, _, d = spans[i]
            active[i] = (n in prog, d, n)
        elif kind == -1:
            active.pop(i, None)
        else:
            idle_on = kind == 2
    return out


def _name_at(t: int, spans) -> str:
    prog = set(PROGRAM_SPANS)
    cover = [(n not in prog, d, n) for n, s, d in spans if s <= t < s + d]
    return min(cover)[2] if cover else "none"


def reduce(ev: Dict[str, List[Any]], top: int = 10) -> Dict[str, Any]:
    """The new readings of one traced window (``{}`` without one)."""
    lo, hi = _window(ev)
    if hi <= lo or not (ev["ops"] or ev["modules"]):
        return {}
    ns = 1e-9
    host = _inside([e for e in ev["host"] if e[0] != "window"], lo, hi)
    steps = sorted(e for e in host if e[0] == STEP)
    nested = [e for e in host if e[0] in (WAIT, PLAN)]
    by_step = []
    for _, s, d in steps:
        within = [e for e in nested if s <= e[1] and e[1] + e[2] <= s + d]
        by_step.append([d * ns,
                        sum(e[2] for e in within if e[0] == WAIT) * ns,
                        sum(e[2] for e in within if e[0] == PLAN) * ns])
    gaps = [(b[1] - (a[1] + a[2])) * ns for a, b in zip(steps, steps[1:])]
    idle = _idle(ev, lo, hi)
    spans = [e for e in ev["host"] if e[0] != "window"]
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:top]
    named = [(_name_at((a + b) // 2, spans), b - a) for a, b in longest]
    return {
        "steps": by_step,
        "step_gaps_s": gaps,
        "scoped_device_s": _scoped_self_ns(ev["ops"], lo, hi) * ns,
        "idle_in_span": {n: t * ns
                         for n, t in _split_idle(idle, spans).items()},
        "idle_gaps": [[n, d * ns] for n, d in named],
    }
