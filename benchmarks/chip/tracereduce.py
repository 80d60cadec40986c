"""Reduce a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes one ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  Device planes are named ``/device:TPU:<n>``.  Their
``XLA Ops`` line holds one event per operation run, named by the
optimized HLO's text for the instruction (``%mpmm.16 = bf16[...] ...``);
their ``XLA Modules`` line one event per program run.

The host tracer is off in a traced run: on this system it records every
chunk of the host-side layout transpose of each input batch, hundreds of
thousands of events a second, which slows the host loop ten times over.
So the harness keeps its own log of what the host did, on its own clock,
and bounds the traced stretch on the device by two runs of a tiny marker
program, whose first run also ties the two clocks together.

Everything here works on plain lists of ``(name, start_s, end_s)``, so
a small recorded trace tests it without a chip.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]   # (name, start seconds, end seconds)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARKER = "chipbench_mark"


def _events(line, name=lambda n: n) -> List[Event]:
    return sorted(((name(e.name), e.start_ns * 1e-9,
                    (e.start_ns + e.duration_ns) * 1e-9) for e in line.events),
                  key=lambda e: e[1])


def events_from_profile(pd) -> Dict[str, Dict[str, List[Event]]]:
    """{device plane: {"ops": op events, "modules": program runs}}."""
    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        dev = out.setdefault(plane.name, {"ops": [], "modules": []})
        for line in plane.lines:
            if line.name == OPS_LINE:
                dev["ops"] += _events(line, op_name)
            elif line.name == MODULES_LINE:
                dev["modules"] += _events(line)
    return out


def stretch(modules: Sequence[Event]) -> Optional[Tuple[float, float, float]]:
    """(start, end, first marker's start) of the traced stretch on the
    device clock: from the end of the first marker run to the start of
    the last."""
    marks = [e for e in modules if MARKER in e[0]]
    if len(marks) < 2:
        return None
    return marks[0][2], marks[-1][1], marks[0][1]


def clip(events: Sequence[Event], t0: float, t1: float) -> List[Event]:
    """Events cut to the window [t0, t1]; those outside it dropped."""
    return [(n, max(a, t0), min(b, t1)) for n, a, b in events
            if b > t0 and a < t1]


def union(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """Merged busy intervals of possibly overlapping events."""
    out: List[List[float]] = []
    for _, a, b in sorted(events, key=lambda e: e[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_seconds(events: Sequence[Event]) -> float:
    return sum(b - a for a, b in union(events))


def op_name(event_name: str) -> str:
    """The HLO instruction an ``XLA Ops`` event ran: its name is the
    instruction's text, ``%mpmm.16 = bf16[...] custom-call(...)``."""
    return event_name.split(" = ")[0].strip().lstrip("%")


def kernel_of(name: str) -> str:
    """``mpmm.16`` -> ``mpmm``: an op's family, without its instance."""
    return name.split(".")[0]


def time_by(events: Sequence[Event]) -> Dict[str, float]:
    """Device seconds of each op family."""
    out: Dict[str, float] = defaultdict(float)
    for n, a, b in events:
        out[kernel_of(n)] += b - a
    return dict(out)


def gaps(events: Sequence[Event], t0: float, t1: float
         ) -> List[Tuple[float, float]]:
    """Idle intervals of the device inside [t0, t1]."""
    out, t = [], t0
    for a, b in union(events):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < t1:
        out.append((t, t1))
    return out


def host_activity(host: Sequence[Event], t: float) -> str:
    """The innermost entry of the harness's host log open at ``t``."""
    best: Optional[Event] = None
    for ev in host:
        if ev[1] <= t <= ev[2] and (best is None or ev[1] >= best[1]):
            best = ev
    return best[0] if best else "outside the harness"


def breakdown(events: Sequence[Event], host: Sequence[Event],
              t0: float, t1: float, top: int = 10) -> Dict[str, list]:
    """The device ops that took most time, by family, and the longest
    idle gaps, each named by what the host was doing at its middle."""
    ops = sorted(time_by(events).items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps(events, t0, t1), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[host_activity(host, (a + b) / 2), b - a]
                          for a, b in idle]}
