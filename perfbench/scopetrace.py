"""Profiler trace -> device operations by plan stage, and the service's
own ``lookup.*`` annotations, on the profiler's clock.

`read` takes the ``.xplane.pb`` that `jax.profiler` writes, as
`tracefile.read` does, and keeps what that reduction drops:

  ops      the operations of each device plane (``/device:TPU:<i>``,
           line "XLA Ops") as ``(name, start_ns, dur_ns, scope)``, where
           ``scope`` is the operation's scope path, the ``op_name`` of
           its HLO instruction (for example
           ``jit(run_instr)/jit(main)/health_stats/predict/mul``);
  lookup   the host annotations the service's span recorder writes
           (``lookup.pin``, ``lookup.launch``, ...) as ``(name,
           start_ns, dur_ns, args)``, ``args`` holding their stats
           (``batch`` first among them).

The TPU writes no scope on an operation's event: its stats are only its
device offset and duration.  The trace holds each program's HLO instead
(the ``Hlo Proto`` stat of the ``/host:metadata`` plane, one per
``<module>(<program id>)``), and the device plane's "XLA Modules" line
says which program ran when; an operation's scope is the ``op_name`` of
the instruction of that name in the program that was running.

The plan program names its stages with `jax.named_scope`
(``predict``, ``last_mile``, ``health_stats``, ``merge``; the stats'
own predict on the Pallas path is ``health_stats/predict``), so
`stage_ns` tells the stages apart by name, not by fusion names.
`idle_while` gives the device idle time during given host intervals:
the ``lookup.pin``/``gather``/``launch`` annotations on the profiler's
own clock, or program spans put on it by the anchor offset.

    python3 perfbench/scopetrace.py <profile dir>

prints each stage's device time and the idle time while the dispatch
thread pinned, gathered or launched, for a trace of a running service.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import sys
from typing import Dict, Iterator, List, Sequence, Tuple

if __name__ == "__main__":
    sys.path[:0] = [os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))]

from perfbench import tracefile  # noqa: E402

Op = Tuple[str, float, float, str]          # (name, start_ns, dur_ns, scope)
Annotation = Tuple[str, float, float, Dict]  # (name, start_ns, dur_ns, args)

LOOKUP_PREFIX = "lookup."
MODULES_LINE = "XLA Modules"
#: the dispatch thread's phases before a batch's program is enqueued
DISPATCH = ("pin", "gather", "launch")


def read(log_dir: str) -> dict:
    """``{"ops": {plane: [Op]}, "lookup": [Annotation]}`` from the one
    ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    with open(paths[0], "rb") as f:
        raw = f.read()
    names = op_names(raw)
    data = ProfileData.from_serialized_xspace(raw)
    ops: Dict[str, List[Op]] = {}
    lookup: List[Annotation] = []
    for plane in data.planes:
        if plane.name.startswith(tracefile.DEVICE_PREFIX) and \
                plane.name[len(tracefile.DEVICE_PREFIX):].isdigit():
            lines = {line.name: line for line in plane.lines}
            if tracefile.OPS_LINE in lines:
                ops[plane.name] = _scoped(
                    [(e.name, float(e.start_ns), float(e.duration_ns))
                     for e in lines[tracefile.OPS_LINE].events],
                    [(e.name, float(e.start_ns), float(e.duration_ns))
                     for e in lines[MODULES_LINE].events]
                    if MODULES_LINE in lines else [], names)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                lookup.extend(
                    (e.name, float(e.start_ns), float(e.duration_ns),
                     dict(e.stats))
                    for e in line.events
                    if e.name.startswith(LOOKUP_PREFIX))
    return {"ops": ops, "lookup": sorted(lookup, key=lambda a: a[1])}


def _scoped(events, modules, names: Dict[str, Dict[str, str]]) -> List[Op]:
    """Each operation with the ``op_name`` of its instruction in the
    program whose "XLA Modules" event holds its start ("" if none)."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    out = []
    for name, s, d in events:
        k = bisect.bisect_right(starts, s) - 1
        scope = ""
        if k >= 0 and s < modules[k][1] + modules[k][2]:
            scope = names.get(modules[k][0], {}).get(
                tracefile.instruction(name), "")
        out.append((name, s, d, scope))
    return out


# -- the few protobuf messages read here (xplane.proto, hlo.proto) -----------
def _fields(buf) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview for a length-delimited field; fixed-width
    fields are skipped."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, val


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return out, i


def op_names(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """``{"<module>(<program id>)": {instruction: op_name}}`` from the
    ``Hlo Proto`` stats of the ``/host:metadata`` plane."""
    out: Dict[str, Dict[str, str]] = {}
    for num, plane in _fields(xspace):                 # XSpace.planes
        if num != 1:
            continue
        fields = list(_fields(plane))
        if not any(n == 2 and bytes(v) == b"/host:metadata"
                   for n, v in fields):
            continue
        stat_names = {}
        for n, entry in fields:                        # XPlane.stat_metadata
            if n == 5:
                meta = dict(_fields(dict(_fields(entry))[2]))
                stat_names[meta.get(1)] = bytes(meta.get(2, b"")).decode()
        for n, entry in fields:                        # XPlane.event_metadata
            if n != 4:
                continue
            meta = list(_fields(dict(_fields(entry))[2]))
            name = next((bytes(v).decode() for k, v in meta if k == 2), "")
            for k, stat in meta:                       # XEventMetadata.stats
                st = dict(_fields(stat)) if k == 5 else {}
                if stat_names.get(st.get(1)) == "Hlo Proto" and 6 in st:
                    out[name] = _hlo_op_names(st[6])
    return out


def _hlo_op_names(hlo_proto) -> Dict[str, str]:
    """``{instruction name: metadata.op_name}`` over every computation
    of an ``HloProto``'s module."""
    out = {}
    module = dict(_fields(hlo_proto)).get(1, b"")       # HloProto.hlo_module
    for n, comp in _fields(module):
        if n != 3:                                     # .computations
            continue
        for m, instr in _fields(comp):
            if m != 2:                                 # .instructions
                continue
            f = dict(_fields(instr))
            meta = dict(_fields(f.get(7, b"")))        # .metadata
            if 2 in meta:                              # OpMetadata.op_name
                out[bytes(f[1]).decode()] = bytes(meta[2]).decode()
    return out


def under(scope: str, stage: str) -> bool:
    """Whether a scope path holds ``stage`` as one of its parts."""
    return stage in scope.split("/")


def stage_ns(ops: Sequence[Op], t0: float, t1: float, stage: str,
             outside: str = "") -> float:
    """Summed device time, clipped to [t0, t1], of the operations under
    ``stage`` and, where ``outside`` is given, not under ``outside``."""
    return sum(min(s + d, t1) - max(s, t0) for _, s, d, scope in ops
               if s < t1 and s + d > t0 and under(scope, stage)
               and not (outside and under(scope, outside)))


def intervals(annotations: Sequence[Annotation], names: Sequence[str]
              ) -> List[Tuple[float, float]]:
    """``(start, end)`` of the ``lookup.<name>`` annotations, ``name``
    in ``names``."""
    want = {LOOKUP_PREFIX + n for n in names}
    return [(s, s + d) for n, s, d, _ in annotations if n in want]


def idle_while(events: Sequence, spans: Sequence[Tuple[float, float]],
               t0: float, t1: float) -> float:
    """Nanoseconds of [t0, t1] in which no operation of ``events`` (whose
    first three fields are name, start and duration) ran while one of
    ``spans`` was open."""
    gaps = tracefile.gaps([e[:3] for e in events], t0, t1)
    open_ = tracefile.merged([("", a, b - a) for a, b in spans], t0, t1)
    # both lists are sorted and disjoint: one merge pass
    total, i, j = 0.0, 0, 0
    while i < len(gaps) and j < len(open_):
        (a, b), (c, d) = gaps[i], open_[j]
        total += max(0.0, min(b, d) - max(a, c))
        if b < d:
            i += 1
        else:
            j += 1
    return total


def summary(raw: dict) -> dict:
    """Per device plane: each stage's device seconds, and the idle
    seconds while a dispatch annotation was open, over the span from
    the first to the last annotation."""
    ann = raw["lookup"]
    if not ann:
        return {}
    t0 = min(s for _, s, _, _ in ann)
    t1 = max(s + d for _, s, d, _ in ann)
    out = {}
    for plane, ops in raw["ops"].items():
        out[plane] = {
            "window_s": (t1 - t0) / 1e9,
            "predict_s": stage_ns(ops, t0, t1, "predict",
                                  "health_stats") / 1e9,
            "last_mile_s": stage_ns(ops, t0, t1, "last_mile") / 1e9,
            "health_stats_s": stage_ns(ops, t0, t1, "health_stats") / 1e9,
            "merge_s": stage_ns(ops, t0, t1, "merge") / 1e9,
            "idle_while_dispatch_s": idle_while(
                ops, intervals(ann, DISPATCH), t0, t1) / 1e9,
        }
    return out


if __name__ == "__main__":
    print(json.dumps(summary(read(sys.argv[1])), indent=1))
