"""Reduction of a JAX profiler trace to device busy time, per-program device
time, the busiest device operations and the longest idle gaps.

The trace is read with ``jax.profiler.ProfileData`` into plain events
``{"plane", "line", "name", "start_ns", "dur_ns"}`` (``load_events``); the
reduction (``reduce``) works on that list, so that a test can hand it one.

* Device planes are named ``/device:<KIND>:<n>``.  An operation runs on the
  device in the events of each device plane's ``XLA Ops`` line; busy time
  is the union of their intervals inside the window, averaged over the
  device planes.
* A program's device time is the sum of its events on the ``XLA Modules``
  line, keyed by the module name without its ``(<id>)`` suffix.
* The window is the host span the harness opens around the traced work
  (``WINDOW``), on the same clock as the device events.  Idle gaps are
  labelled by the innermost harness annotation (``harness:*``) that covers
  the gap's middle.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

from intervals import covered, gaps

__all__ = ["WINDOW", "load_events", "reduce"]

WINDOW = "harness:window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"\(\d+\)$")


def load_events(trace_dir: str) -> list:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "start_ns": ev.start_ns,
                            "dur_ns": ev.duration_ns})
    return out


def _is_device(plane: str) -> bool:
    return plane.startswith("/device:") and not plane.startswith(
        "/device:CPU")


def reduce(events: list, top: int = 10) -> dict:
    """``window_s``, ``busy_s`` (mean over devices), ``devices``,
    ``modules_s`` (program -> device seconds), ``device_ops`` and
    ``idle_gaps`` (each the ``top`` largest, ``[name, seconds]``)."""
    windows = [e for e in events if e["name"] == WINDOW
               and not _is_device(e["plane"])]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW!r} span")
    w = max(windows, key=lambda e: e["dur_ns"])
    w0, w1 = w["start_ns"], w["start_ns"] + w["dur_ns"]

    def inside(e):
        s = max(e["start_ns"], w0)
        return s, min(e["start_ns"] + e["dur_ns"], w1)

    busy_by_plane = defaultdict(list)
    op_time = defaultdict(float)
    modules = defaultdict(float)
    for e in events:
        if not _is_device(e["plane"]):
            continue
        s, t = inside(e)
        if t <= s:
            continue
        if e["line"] == OPS_LINE:
            busy_by_plane[e["plane"]].append((s, t))
            op_time[e["name"]] += (t - s) * 1e-9
        elif e["line"] == MODULES_LINE:
            modules[_SUFFIX.sub("", e["name"])] += (t - s) * 1e-9
    planes = sorted({e["plane"] for e in events if _is_device(e["plane"])})
    busy = [covered(busy_by_plane[p]) for p in planes]
    all_busy = [iv for p in planes for iv in busy_by_plane[p]]
    marks = [e for e in events if e["name"].startswith("harness:")
             and e["name"] != WINDOW and not _is_device(e["plane"])]
    labelled = defaultdict(float)
    for s, t in gaps(all_busy, w0, w1):
        mid = (s + t) / 2
        over = [m for m in marks
                if m["start_ns"] <= mid <= m["start_ns"] + m["dur_ns"]]
        name = (min(over, key=lambda m: m["dur_ns"])["name"] if over
                else "outside harness spans")
        labelled[name] += (t - s) * 1e-9
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": (sum(busy) / len(busy) * 1e-9) if busy else 0.0,
        "devices": len(planes),
        "modules_s": dict(modules),
        "device_ops": sorted(([n, s] for n, s in op_time.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": sorted(([n, s] for n, s in labelled.items()),
                            key=lambda x: -x[1])[:top],
    }
