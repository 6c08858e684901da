"""Profiler trace -> device operations -> busy time, idle gaps, kernel time.

`read` takes the ``.xplane.pb`` that `jax.profiler` writes and keeps
what the metrics need: the operations of each device plane
(``/device:TPU:<i>``, line "XLA Ops") and the host annotations this
benchmark wrote (``perfbench.*``), each as ``(name, start_ns, dur_ns)``
on the profiler's clock.  Everything else here works on that reduced
form, so it is tested on a small recorded trace (`tests/data/`).

Busy time is the union of the intervals in which an operation ran on a
device, clipped to the traced window; idle share is 1 minus busy over
the window.  On a TPU an operation's name is its HLO instruction
(``%bounded_search.1 = s32[2048]... custom-call(...)``); kernel time is
the summed duration of the operations whose instruction is the kernel
(``bounded_search``, ``bounded_search.1``, ...), not of those that only
read its result.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, dur_ns)

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
ANNOTATION_PREFIX = "perfbench."


def read(log_dir: str) -> dict:
    """``{"devices": {plane: [Event]}, "host": [Event]}`` from the one
    ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX) and \
                plane.name[len(DEVICE_PREFIX):].isdigit():
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events
                            if e.name.startswith(ANNOTATION_PREFIX))
    return {"devices": devices, "host": host}


def merged(events: Sequence[Event], t0: float, t1: float
           ) -> List[Tuple[float, float]]:
    """Disjoint sorted intervals covered by ``events``, within [t0, t1]."""
    spans = sorted((max(s, t0), min(s + d, t1)) for _, s, d in events
                   if s < t1 and s + d > t0)
    out: List[List[float]] = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(events: Sequence[Event], t0: float, t1: float) -> float:
    """Length of the union of the operations' intervals in [t0, t1]."""
    return sum(b - a for a, b in merged(events, t0, t1))


def gaps(events: Sequence[Event], t0: float, t1: float
         ) -> List[Tuple[float, float]]:
    """The idle intervals of [t0, t1]: where no operation ran."""
    out, cur = [], t0
    for a, b in merged(events, t0, t1):
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if cur < t1:
        out.append((cur, t1))
    return out


def instruction(op: str) -> str:
    """The instruction an operation's name gives: ``bounded_search.1`` of
    ``%bounded_search.1 = s32[2048]... custom-call(...)``."""
    return op.split(" = ", 1)[0].lstrip("%")


def short(op: str) -> str:
    """A readable name: instruction, result type and custom-call target,
    e.g. ``custom-call.1 u32[200000000] X64SplitLow``."""
    head, _, rest = op.partition(" = ")
    out = [head.lstrip("%")]
    m = re.match(r"(.*?) [a-z][\w-]*\(", re.sub(r"\{[^{}]*\}", "", rest))
    if m:
        out.append(m.group(1))
    tgt = op.partition('custom_call_target="')[2].partition('"')[0]
    if tgt:
        out.append(tgt)
    return " ".join(out)


def op_ns(events: Sequence[Event], kernel: str, t0: float, t1: float
          ) -> float:
    """Summed duration, clipped to [t0, t1], of the ``kernel``'s own
    operations."""
    return sum(min(s + d, t1) - max(s, t0) for n, s, d in events
               if instruction(n).split(".", 1)[0] == kernel
               and s < t1 and s + d > t0)


def top_ops(events: Sequence[Event], t0: float, t1: float, k: int = 10
            ) -> List[Tuple[str, float]]:
    """The ``k`` operations (`short` names) with the most device time in
    [t0, t1], with their summed seconds."""
    tot: Dict[str, float] = {}
    for n, s, d in events:
        if s < t1 and s + d > t0:
            n = short(n)
            tot[n] = tot.get(n, 0.0) + min(s + d, t1) - max(s, t0)
    return [(n, ns / 1e9) for n, ns in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def overlap(intervals: Sequence[Tuple[float, float]], a: float, b: float
            ) -> float:
    """How much of [a, b] the (disjoint) ``intervals`` cover."""
    return sum(max(0.0, min(y, b) - max(x, a)) for x, y in intervals)
