"""Reduce a profiler trace (XPlane) to device busy time, kernel time and
the longest idle gaps.

The node captures the trace (``/debug/jax/trace``); the benchmark reads
the ``.xplane.pb`` after the node has exited, with JAX's own reader and
no backend. Everything past :func:`load_events` works on plain tuples,
so the arithmetic is tested on small recorded and hand-made traces.

* Device events are those on the TPU device planes (``/device:TPU:n``),
  on the line of XLA ops where there is one. On the CPU backend, where
  the tests record their traces, they are the XLA executor threads'
  events.
* Busy time is the union of the device events' intervals, averaged over
  the chips; idle is the traced window less that.
* A kernel's time is the sum of the durations of its programs on the
  device (the XLA Modules line). A program is named by its jitted
  function (``jit_decode_fold_raw(<fingerprint>)`` is
  ``decode_fold_raw``). Programs whose function is a generic ``step``
  are named by the ``patrol.<kernel>`` host annotation
  (``PATROL_DEVICE_ANNOTATIONS=1``) around the dispatch that enqueued
  them: the program's ``run_id`` leads to its host enqueue event, and
  the enqueue to the annotation that spans it (else the last one that
  began before it).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: int
    dur_ns: int
    run_id: int = -1


ENQUEUE = "DoEnqueueProgram"


ANNOTATION_PREFIX = "patrol."


def find_xplane(trace_dir: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    return hits[-1] if hits else None


HOST_SPAN_MIN_NS = 100_000  # shorter host events cannot name an idle gap


def load_events(path: str) -> List[Event]:
    """The trace's timed events that the reduction reads, with plane and
    line names: every device event, every ``patrol.`` annotation, the
    CPU backend's executor events, and host events of 100 us or more."""
    from jax.profiler import ProfileData  # reads the file; starts no backend

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        dev = is_device_plane(plane.name)
        for line in plane.lines:
            keep_all = dev or line.name.startswith("tf_XLA")
            for e in line.events:
                dur = int(e.duration_ns)
                name = e.name
                run_id = -1
                if (dev and line.name == "XLA Modules") or name == ENQUEUE:
                    run_id = next((int(v) for k, v in e.stats if k == "run_id"), -1)
                elif not (keep_all or dur >= HOST_SPAN_MIN_NS or name.startswith(ANNOTATION_PREFIX)):
                    continue
                out.append(Event(plane.name, line.name, name, int(e.start_ns), dur, run_id))
    return out


def is_device_plane(plane: str) -> bool:
    return plane.startswith("/device:TPU:")


def device_events(events: Iterable[Event]) -> Dict[str, List[Event]]:
    """Device events per device plane: on a TPU, the XLA ops line of each
    device plane (every line but step markers where it has none); on the
    CPU backend, the XLA executor threads' events with a duration."""
    events = list(events)
    tpu = defaultdict(list)
    for e in events:
        if is_device_plane(e.plane):
            tpu[e.plane].append(e)
    if tpu:
        out = {}
        for plane, evs in tpu.items():
            ops = [e for e in evs if e.line == "XLA Ops"]
            out[plane] = ops or [e for e in evs if e.line not in ("Steps", "XLA TraceMe")]
        return out
    cpu = [
        e for e in events
        if e.line.startswith("tf_XLA") and e.dur_ns > 0 and not e.name.startswith(("ThreadpoolListener", "end: "))
    ]
    return {"cpu": cpu} if cpu else {}


def module_events(events: Iterable[Event]) -> List[Event]:
    """One event per executed program on the device (TPU: the XLA
    Modules line); the CPU backend has none, so its op events stand in."""
    events = list(events)
    mods = [e for e in events if is_device_plane(e.plane) and e.line == "XLA Modules"]
    if mods:
        return mods
    return [e for evs in device_events(events).values() for e in evs]


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged [start, end) intervals, in order."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_ns(evs: Iterable[Event]) -> int:
    return sum(e - s for s, e in union((x.start_ns, x.start_ns + x.dur_ns) for x in evs))


def annotations(events: Iterable[Event]) -> List[Event]:
    return sorted(
        (e for e in events if e.name.startswith(ANNOTATION_PREFIX) and not is_device_plane(e.plane)),
        key=lambda e: e.start_ns,
    )


def program_name(module: str) -> str:
    """``jit_decode_fold_raw(123)`` → ``decode_fold_raw``."""
    base = module.split("(", 1)[0]
    return base[4:] if base.startswith("jit_") else base


def kernel_ns(events: List[Event]) -> Dict[str, int]:
    """Device time per kernel name; a generic ``step`` program takes the
    name of the annotation that launched it (``?`` where none did)."""
    ann = annotations(events)
    starts = [a.start_ns for a in ann]
    enqueued = {e.run_id: e.start_ns for e in events if e.name == ENQUEUE and e.run_id >= 0}

    def launcher(t: int) -> str:
        i = bisect.bisect_right(starts, t) - 1
        for j in range(i, max(i - 8, -1), -1):  # an annotation that spans t
            if ann[j].start_ns <= t <= ann[j].start_ns + ann[j].dur_ns:
                return ann[j].name[len(ANNOTATION_PREFIX):]
        return ann[i].name[len(ANNOTATION_PREFIX):] if i >= 0 else "?"

    out: Dict[str, int] = defaultdict(int)
    for m in module_events(events):
        name = program_name(m.name)
        if not is_device_plane(m.plane) or name == "step":
            name = launcher(enqueued.get(m.run_id, m.start_ns))
        out[name] += m.dur_ns
    return dict(out)


def short_op(name: str) -> str:
    """An HLO op's trace name (its whole instruction text) cut to the
    instruction, its result shape and any custom-call target."""
    if " = " not in name:
        return name
    lhs, rhs = name.split(" = ", 1)
    shape = rhs.split("{", 1)[0].split(" ", 1)[0]
    target = re.search(r'custom_call_target="([^"]+)"', rhs)
    return " ".join(x for x in (lhs, shape, target.group(1) if target else "") if x)


def top_ops(events: List[Event], n: int = 10) -> List[Tuple[str, float]]:
    """The device ops that took most time, each with its program."""
    tot: Dict[str, int] = defaultdict(int)
    mods = sorted(module_events(events), key=lambda m: m.start_ns)
    mstarts = [m.start_ns for m in mods]
    for evs in device_events(events).values():
        for e in evs:
            i = bisect.bisect_right(mstarts, e.start_ns) - 1
            prog = program_name(mods[i].name) if i >= 0 and mods[i] is not e else ""
            tot[(prog + ": " if prog else "") + short_op(e.name)] += e.dur_ns
    return [(k, v / 1e9) for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events: List[Event], window: Tuple[int, int], n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest idle gaps on the busiest device plane within
    ``window``, each named by the shortest host event that spans its
    midpoint (a ``patrol.`` annotation first, where one does)."""
    dev = device_events(events)
    if not dev:
        return []
    plane = max(dev, key=lambda p: busy_ns(dev[p]))
    busy = union((e.start_ns, e.start_ns + e.dur_ns) for e in dev[plane])
    lo, hi = window
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, min(s, hi)))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    gaps = sorted((g for g in gaps if g[1] > g[0]), key=lambda g: g[0] - g[1])[:n]
    host = [e for e in events if not is_device_plane(e.plane) and e.dur_ns > 0]
    out = []
    for s, e in gaps:
        mid = (s + e) // 2
        cover = [h for h in host if h.start_ns <= mid < h.start_ns + h.dur_ns]
        ann = [h for h in cover if h.name.startswith(ANNOTATION_PREFIX)]
        pick = min(ann or cover, key=lambda h: h.dur_ns, default=None)
        out.append((pick.name if pick else "(no host span)", (e - s) / 1e9))
    return out


def reduce(events: List[Event], window_ns: int) -> dict:
    """→ {busy_s, window_s, kernels {name: s}, device_ops, idle_gaps}.
    ``window_ns`` is the capture's length; busy time is averaged over the
    device planes (chips) traced."""
    dev = device_events(events)
    if not dev:
        return {}
    busy = sum(busy_ns(evs) for evs in dev.values()) / len(dev)
    starts = [e.start_ns for evs in dev.values() for e in evs]
    ends = [e.start_ns + e.dur_ns for evs in dev.values() for e in evs]
    # The capture window, placed over the device events it holds.
    lo = min(starts)
    hi = max(max(ends), lo + window_ns)
    return {
        "busy_s": busy / 1e9,
        "window_s": window_ns / 1e9,
        "kernels": {k: v / 1e9 for k, v in kernel_ns(events).items()},
        "device_ops": top_ops(events),
        "idle_gaps": idle_gaps(events, (lo, hi)),
    }
