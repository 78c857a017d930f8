"""Reduce a JAX profiler trace to device busy time, idle gaps and top ops.

The traced window is the host span named ``window`` that the harness opens
around its traced steps.  Busy time is the union of the intervals in which
an operation ran on a device (the ``XLA Ops`` line of each TPU plane),
clipped to that window; the idle gaps are what the union leaves, each
named by the innermost benchmark host span (``courant_dt``, ``rk3_step``,
``dt_sync``) that covers its midpoint, or ``other``.  A device op is named
``<program>/<op>``: the jitted program (``XLA Modules`` line) it ran in,
without its fingerprint, and the HLO instruction's name.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
HOST_SPANS = ("courant_dt", "rk3_step", "dt_sync")


def union(intervals):
    """Merge ``(start, end)`` intervals into sorted disjoint ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def _host_events(profile):
    for plane in profile.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                yield from line.events


def reduce_trace(profile, window: str = "bench_window", top: int = 10):
    """``profile`` is a ``jax.profiler.ProfileData``.  Returns ``None`` when
    the trace has no such window or no device operation inside it, else a
    dict with ``busy_s`` (mean over devices), ``window_s``, ``idle_share``
    (0..1), ``device_ops`` and ``idle_gaps`` (each at most ``top``
    ``[name, seconds]`` pairs, longest first)."""
    spans, win = [], None
    for ev in _host_events(profile):
        if ev.name == window and win is None:
            win = (ev.start_ns, ev.end_ns)
        elif ev.name in HOST_SPANS:
            spans.append((ev.start_ns, ev.end_ns, ev.name))
    if win is None:
        return None
    w0, w1 = win
    busy, ops, gaps = [], defaultdict(float), []
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        modules = sorted((ev.start_ns, ev.end_ns, _program(ev.name))
                         for ev in lines.get(MODULES_LINE, []))
        starts = [m[0] for m in modules]
        ivs = []
        for ev in lines.get(OPS_LINE, []):
            s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if e > s:
                ivs.append((s, e))
                ops[_op_name(ev, modules, starts)] += (e - s) * 1e-9
        merged = union(ivs)
        if not merged:
            continue
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, _label((s + e) / 2, spans)))
    if not busy:
        return None
    window_s = (w1 - w0) * 1e-9
    busy_s = sum(busy) / len(busy)
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    top_gaps = sorted(gaps, key=lambda g: -g[0])[:top]
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s,
            "device_ops": [[name, s] for name, s in top_ops],
            "idle_gaps": [[name, ns * 1e-9] for ns, name in top_gaps]}


def _program(name: str) -> str:
    """``jit_step(1234)`` -> ``jit_step``."""
    return name.split("(", 1)[0]


def _op_name(ev, modules, starts) -> str:
    """``<program>/<op>`` from an op event whose name is its HLO text."""
    op = ev.name.split(" = ", 1)[0].lstrip("%")
    i = bisect.bisect_right(starts, ev.start_ns) - 1
    if i >= 0 and ev.start_ns <= modules[i][1]:
        return f"{modules[i][2]}/{op}"
    return op


def _label(t, spans) -> str:
    """The innermost (shortest) host span that covers time ``t``."""
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "other"
