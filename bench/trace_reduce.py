"""Reduce a profiler trace (``.xplane.pb``) to the numbers the readers use.

Read with ``jax.profiler.ProfileData`` and nothing else. For each device
plane (``/device:TPU:<n>``) the op events on its ``XLA Ops`` line give:

* ``busy_s`` — the union of the op intervals inside the traced window (a
  ``while`` op spans its whole loop, so loop control counts as busy);
* ``kernels`` — per kernel, the summed duration and count of the events
  whose name matches its pattern (``KERNELS``);
* ``ops`` — seconds per op, keyed by its HLO instruction up to the
  opcode (name, result shape, opcode), leaving out the control-flow ops
  that contain others (``while``, ``conditional``, ``call``);
* ``modules`` — how many times each program ran (the ``XLA Modules``
  line), by its name;
* ``gaps`` — the longest idle gaps inside the window.

The profiler keeps a bounded number of device events (about 6.2 million
on a TPU v5 lite with this JAX, measured), and drops the rest: a traced
job longer than about 20 s loses its end. So each device is measured over
its *span*, from the window's start to the end of its last recorded op;
``idle_s`` is the span less ``busy_s``. The host's last result
conversion, after the device's last op, falls outside the span.

An op event's name is its HLO instruction text (``%fusion.3 = s32[..]
fusion(..), ..``). The fused kernel has no name of its own yet: it is the
one Mosaic custom call (``custom_call_target="tpu_custom_call"``) on the
benchmark's timed paths.

The window is the span of the host annotation :data:`WINDOW_SPAN`, which
the harness wraps round the traced jobs. Each idle gap is named by the
shortest event of the host's Python thread that covers its midpoint (the
harness marks each job, and JAX marks dispatches and transfers): what the
host was doing while the device waited.
"""

from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

#: the host annotation round the traced jobs
WINDOW_SPAN = "bench.traced_window"
#: the device line that holds one event per executed op
OPS_LINE = "XLA Ops"
#: the device line that holds one event per program run
MODULES_LINE = "XLA Modules"
#: kernel name -> pattern of its op events' names
KERNELS = {"fused_fsm": r'custom_call_target="tpu_custom_call"'}
#: the host line whose events name the idle gaps
HOST_LINE = "python"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPCODE = re.compile(r" = .*? ([a-z][a-z0-9-]*)\(")
CONTAINERS = ("while", "conditional", "call")


def find_xplane(log_dir: str) -> Path:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    found = sorted(Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def reduce_xplane(path, kernels: Optional[Dict[str, str]] = None) -> Dict:
    """Per-device busy, kernel and idle numbers of one trace.

    Returns ``{"window_s", "devices": [{"device", "events", "first_s",
    "span_s", "busy_s", "idle_s", "kernels": {name: {"seconds",
    "events"}}, "modules": {program: runs}, "ops": {op: seconds}, "gaps":
    [(seconds, host activity), ...]}]}``, devices in id order and gaps
    longest first; ``first_s`` is the first busy instant from the window's
    start. Raises when the window annotation is missing."""
    from jax.profiler import ProfileData

    kernels = KERNELS if kernels is None else kernels
    pats = {k: re.compile(v) for k, v in kernels.items()}
    data = ProfileData.from_file(str(path))
    window = None
    host: List[Tuple[int, int, str]] = []
    planes = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            planes.append((int(m.group(1)), plane))
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e = int(ev.start_ns), int(ev.end_ns)
                if ev.name == WINDOW_SPAN:
                    window = (s, e)
                elif e > s and line.name == HOST_LINE:
                    host.append((s, e, ev.name))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    w0, w1 = window
    devices = []
    for dev_id, plane in sorted(planes, key=lambda x: x[0]):
        ivals = []
        n_events = 0
        ops: Dict[str, float] = defaultdict(float)
        kern = {k: {"seconds": 0.0, "events": 0} for k in pats}
        modules: Dict[str, int] = defaultdict(int)
        for line in plane.lines:
            if line.name == MODULES_LINE:
                for ev in line.events:
                    if w0 <= int(ev.start_ns) < w1:
                        modules[ev.name] += 1
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s = max(int(ev.start_ns), w0)
                e = min(int(ev.end_ns), w1)
                if e <= s:
                    continue
                ivals.append((s, e))
                n_events += 1
                m = OPCODE.search(ev.name)
                if not (m and m.group(1) in CONTAINERS):
                    ops[_op_key(ev.name, m)] += (e - s) * 1e-9
                for k, pat in pats.items():
                    if pat.search(ev.name):
                        kern[k]["seconds"] += (e - s) * 1e-9
                        kern[k]["events"] += 1
        busy = _union(ivals)
        busy_ns = sum(e - s for s, e in busy)
        end = busy[-1][1] if busy else w0
        edges = [w0] + [x for iv in busy for x in iv] + [end]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        devices.append({
            "device": dev_id,
            "events": n_events,
            "first_s": (busy[0][0] - w0) * 1e-9 if busy else None,
            "span_s": (end - w0) * 1e-9,
            "busy_s": busy_ns * 1e-9,
            "idle_s": (end - w0 - busy_ns) * 1e-9,
            "kernels": kern,
            "modules": dict(modules),
            "ops": dict(ops),
            "gaps": [((e - s) * 1e-9, _host_activity(host, (s + e) // 2))
                     for s, e in gaps[:10]],
        })
    return {"window_s": (w1 - w0) * 1e-9, "devices": devices}


def _op_key(text: str, m) -> str:
    """An op's HLO instruction up to its opcode (name, result shape,
    opcode), cut to 160 characters."""
    return (text[:m.end() - 1] if m else text)[:160]


def _host_activity(host: List[Tuple[int, int, str]], t: int) -> str:
    covering = [(e - s, name) for s, e, name in host if s <= t < e]
    return min(covering)[1] if covering else "no host event"
