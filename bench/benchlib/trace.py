"""From a profiler trace to the numbers the per-layer metrics read.

Two steps, kept apart so the second can be checked on a small recorded
trace (``bench/tests/data/trace_small.json``):

* :func:`extract` reads the ``.xplane.pb`` that ``jax.profiler`` wrote
  (``jax.profiler.ProfileData``) into plain event lists: the first
  TPU's ops and modules, and the host's annotation spans. All times are
  nanoseconds on the trace's one clock.
* :func:`reduce` turns those lists into busy and idle time, the round
  programs' and the planner kernel's device time, the busiest ops, and
  the idle gaps named by the host span they fell in.

The names it looks for are below, in one place.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Tuple

#: XLA modules of a scheduler round: the engine's jitted round bodies
#: (``SlotServeEngine._bucket_round_impl`` and its unbucketed and
#: chunk-free variants), as ``jit_<function name>``.
ROUND_PROGRAMS = ("jit__bucket_round_impl", "jit__round_impl",
                  "jit__bucket_chunk_impl", "jit__chunk_impl")
#: XLA modules of the admission planner: the jitted wrapper
#: ``kernels/semaphore/kernel.py::semaphore_admission`` around the Pallas
#: kernel (a ``tpu_custom_call`` of the same name) and its padding.
PLANNER_PROGRAMS = ("jit_semaphore_admission",)
#: The benchmark's own host spans (``drive.py``), innermost first.
HOST_SPANS = ("client", "submit", "hook", "round", "window")
#: Device plane and the lines of ops and of modules on it.
DEVICE_PLANE = re.compile(r"^/device:TPU:0$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Event = Tuple[str, int, int]            # (name, start_ns, duration_ns)


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def extract(path: str) -> Dict[str, List[Event]]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out: Dict[str, List[Event]] = {"ops": [], "modules": [], "host": []}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    out[key] += [(e.name, int(e.start_ns),
                                  int(e.duration_ns)) for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out["host"] += [(e.name, int(e.start_ns), int(e.duration_ns))
                                for e in line.events
                                if e.name in HOST_SPANS]
    return out


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(ev: List[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    out = []
    for _, s, d in ev:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    return out


def short_name(op: str) -> str:
    """``%fusion.12 = bf16[16,40,128]{...} fusion(...)`` ->
    ``%fusion.12 bf16[16,40,128]``: the op and its result's type."""
    name, _, rest = op.partition(" = ")
    if not rest:
        return op[:120]
    head = rest.split("{")[0].split(" ")[0]
    return f"{name} {head}"[:120]


def _self_times(events: List[Event]) -> List[Tuple[str, int]]:
    """Each op's time less the ops nested in it (a ``while`` holds the
    ops of its body on the same line)."""
    out: List[Tuple[str, int]] = []
    stack: List[List[Any]] = []            # [name, end, self_ns]
    for n, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and s >= stack[-1][1]:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            stack[-1][2] -= min(d, stack[-1][1] - s)
        stack.append([short_name(n), s + d, d])
    out += [(t[0], t[2]) for t in stack]
    return out


def reduce(ev: Dict[str, List[Event]], top: int = 10) -> Dict[str, Any]:
    """Busy and idle time of the device within the traced window (the
    host span ``window``), and where the device time went."""
    win = [e for e in ev["host"] if e[0] == "window"]
    if not win or not (ev["ops"] or ev["modules"]):
        return {}
    lo, hi = win[0][1], win[0][1] + win[0][2]
    busy_ev = ev["ops"] or ev["modules"]
    busy = _union(_clip(busy_ev, lo, hi))
    busy_ns = sum(e - s for s, e in busy)

    def total(events, names):
        return sum(b - a for (n, s, d) in events
                   if any(n.startswith(p) for p in names)
                   for a, b in _clip([(n, s, d)], lo, hi))

    by_op: Dict[str, int] = {}
    for n, t in _self_times([(n, s, min(d, hi - s)) for n, s, d in ev["ops"]
                             if lo <= s < hi]):
        by_op[n] = by_op.get(n, 0) + t
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]

    # idle gaps, each named by the innermost host span covering its middle
    spans = sorted((e for e in ev["host"] if e[0] != "window"),
                   key=lambda e: e[2])
    gaps: List[Tuple[str, int]] = []
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) // 2
        name = next((n for n, s, d in spans if s <= mid < s + d), "none")
        gaps.append((name, b - a))
    gaps.sort(key=lambda g: -g[1])
    idle_by: Dict[str, int] = {}
    for n, d in gaps:
        idle_by[n] = idle_by.get(n, 0) + d
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": busy_ns * ns,
        "round_device_s": total(ev["modules"], ROUND_PROGRAMS) * ns,
        "round_modules": sum(1 for n, s, d in ev["modules"]
                             if any(n.startswith(p) for p in ROUND_PROGRAMS)
                             and lo <= s < hi),
        "planner_device_s": total(ev["modules"], PLANNER_PROGRAMS) * ns,
        "planner_modules": sum(1 for n, s, d in ev["modules"]
                               if any(n.startswith(p)
                                      for p in PLANNER_PROGRAMS)
                               and lo <= s < hi),
        "device_ops": [[n, d * ns] for n, d in ops],
        "idle_gaps": [[n, d * ns] for n, d in gaps[:top]],
        "idle_by_span": {n: d * ns for n, d in idle_by.items()},
    }
