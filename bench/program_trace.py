"""Reduce a saved trace to the program's own spans and named programs.

    python3 bench/program_trace.py <trace.xplane.pb> [<trace.xplane.pb> ...]

prints one JSON object per trace.  Where ``trace_reduce`` reads the
device's busy time and names idle gaps by the benchmark's host spans
alone, this reads what the program records inside ``rk3_step``:

* ``span_self_s``, ``span_total_s``, ``span_count``: for each span in the
  traced window (``bench_window``), the benchmark's (``courant_dt``,
  ``rk3_step``, ``dt_sync``) and the program's (``repro.*``, metadata
  after ``#`` stripped), its summed self time, summed duration and number.
  Self time is a span's duration less the part its child spans on the same
  host thread cover;
* ``program_device_s``: device seconds of every jitted program (the
  ``jit_<name>`` of ``XLA Modules``, fingerprint stripped): the union of
  its operations' intervals clipped to the window (an op such as a
  ``while`` encloses the ops of its body), mean over devices;
* ``idle_gaps``: the ``top`` longest gaps of device time in the window,
  each named by the innermost benchmark or program span that covers its
  midpoint, or ``other``.

A trace with no device plane (a CPU run) still gives the span keys.  A
trace recorded by ``bench/calibrate.py --save-trace`` is the usual input.
"""
from __future__ import annotations

import bisect
import json
import sys
from collections import defaultdict

import trace_reduce

PROGRAM_PREFIX = "repro."


def host_spans(profile, window: str = "bench_window"):
    """The window's ``(start_ns, end_ns)`` and the ``(start_ns, end_ns,
    name, thread)`` of every benchmark and program span inside it (clipped
    to it); ``None`` when the trace has no such window."""
    win, events = None, []
    for plane in profile.planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for thread, line in enumerate(plane.lines):
            for ev in line.events:
                name = ev.name.split("#", 1)[0]
                if name == window and win is None:
                    win = (ev.start_ns, ev.end_ns)
                elif (name in trace_reduce.HOST_SPANS
                      or name.startswith(PROGRAM_PREFIX)):
                    events.append((ev.start_ns, ev.end_ns, name, thread))
    if win is None:
        return None
    w0, w1 = win
    return win, [(max(s, w0), min(e, w1), name, thread)
                 for s, e, name, thread in events if min(e, w1) > max(s, w0)]


def self_times(spans) -> dict:
    """Summed self time in seconds per span name: duration less the part
    covered by direct children on the same thread."""
    out = defaultdict(float)
    threads = defaultdict(list)
    for s, e, name, thread in spans:
        threads[thread].append((s, e, name))

    def close(frame):
        s, e, name, covered = frame
        out[name] += (e - s - covered) * 1e-9

    for evs in threads.values():
        stack = []                       # [start, end, name, covered]
        for s, e, name in sorted(evs, key=lambda v: (v[0], -v[1])):
            while stack and stack[-1][1] <= s:
                close(stack.pop())
            if stack:
                stack[-1][3] += min(e, stack[-1][1]) - s
            stack.append([s, e, name, 0])
        while stack:
            close(stack.pop())
    return dict(out)


def device_programs(profile, window, spans, top: int = 10):
    """``program_device_s`` and ``idle_gaps`` (see the module's doc) of
    the device planes inside ``window`` = ``(start_ns, end_ns)``."""
    w0, w1 = window
    programs, gaps, planes = defaultdict(float), [], 0
    for plane in profile.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        modules = sorted((ev.start_ns, ev.end_ns,
                          trace_reduce._program(ev.name))
                         for ev in lines.get(trace_reduce.MODULES_LINE, []))
        starts = [m[0] for m in modules]
        ivs, by_program = [], defaultdict(list)
        for ev in lines.get(trace_reduce.OPS_LINE, []):
            s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if e > s:
                ivs.append((s, e))
                i = bisect.bisect_right(starts, ev.start_ns) - 1
                program = (modules[i][2] if i >= 0
                           and ev.start_ns <= modules[i][1] else "other")
                by_program[program].append((s, e))
        merged = trace_reduce.union(ivs)
        if not merged:
            continue
        planes += 1
        for program, program_ivs in by_program.items():
            programs[program] += sum(
                e - s for s, e in trace_reduce.union(program_ivs)) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(e - s, (s + e) / 2)
                 for s, e in zip(edges[::2], edges[1::2]) if e > s]
    labelled = [(s, e, name) for s, e, name, _ in spans]
    top_gaps = sorted(gaps, key=lambda g: -g[0])[:top]
    return ({name: sec / planes for name, sec in programs.items()},
            [[trace_reduce._label(mid, labelled), ns * 1e-9]
             for ns, mid in top_gaps])


def reduce_program(profile, window: str = "bench_window", top: int = 10):
    """The keys of the module's doc for ``profile`` (a
    ``jax.profiler.ProfileData``); ``None`` when it has no ``window``."""
    found = host_spans(profile, window)
    if found is None:
        return None
    win, spans = found
    total, count = defaultdict(float), defaultdict(int)
    for s, e, name, _ in spans:
        total[name] += (e - s) * 1e-9
        count[name] += 1
    programs, gaps = device_programs(profile, win, spans, top)
    return {"window_s": (win[1] - win[0]) * 1e-9,
            "span_self_s": self_times(spans), "span_total_s": dict(total),
            "span_count": dict(count), "program_device_s": programs,
            "idle_gaps": gaps}


if __name__ == "__main__":
    from jax.profiler import ProfileData
    for path in sys.argv[1:]:
        print(json.dumps({"trace": path, **reduce_program(
            ProfileData.from_file(path))}), flush=True)
