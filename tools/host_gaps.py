#!/usr/bin/env python3
"""What the host was doing while the device waited.

    python3 tools/host_gaps.py <trace dir or .xplane.pb> [--device TPU|CPU] [--json]

``benchmark/run.py --trace 1`` leaves the profiler's xplane under
``.bench_runs/<cell>/trace/``. The engine thread's phases are spans on its
host plane (``engine.*``, ``setup.*``: runtime/profiling.py:PhaseClock), on the
same clock as device 0's module events. This reads both and prints

  (a) the longest gaps between module events on device 0, each with the
      innermost span that covers most of it (and how long after the host's
      last dispatch call began the gap ended),
  (b) the device's idle seconds by innermost span name,
  (c) per span name: count, total and self seconds (and for the spans JAX
      itself writes on that thread, ``PjitFunction(...)``, the phase most of
      them sit in),
  (d) whether the two planes share a clock: the k-th module event of a step
      program against the k-th ``engine.*.dispatch`` span.

The join is arithmetic on ``[name, start_ns, dur_ns]`` lists, as
``benchmark/trace_reduce.py``'s is, so it is tested on hand-made ones.
``--device CPU`` reads a rehearsal: there the "device" is the threads of
XLA's CPU client (no device plane), which is enough to debug this file and
never a device number.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import re
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.trace_reduce import find_xplane, module_kind  # noqa: E402

OURS = ("engine.", "setup.")
ROOT_SPAN = "engine.step"
# the module events of the step programs, and the span each is called in
PROGRAMS = {"jit_chunk": "engine.chunk.dispatch", "jit_decode": "engine.decode.dispatch"}


def innermost_segments(spans: list) -> list:
    """Nested spans of one thread -> ``[name, start, end]`` stretches, each
    named by the innermost span open in it (a span's stretches are its self
    time). Stretches under no span are left out."""
    out, stack = [], []  # stack of [name, end], innermost last
    at = None

    def emit(until):
        nonlocal at
        if stack and until > at:
            out.append([stack[-1][0], at, until])
        at = until

    for name, start, dur in sorted(spans, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            emit(stack[-1][1])
            stack.pop()
        if stack:
            emit(start)
        at = start
        # a child may not outlive its parent (clock jitter at the edges)
        end = min(start + dur, stack[-1][1]) if stack else start + dur
        stack.append([name, end])
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return out


def device_gaps(modules: list) -> list:
    """``[start, end, ended_by]`` of every stretch in which no module ran."""
    gaps, end = [], None
    for name, start, dur in sorted(modules, key=lambda e: e[1]):
        if end is not None and start > end:
            gaps.append([end, start, module_kind(name)])
        end = max(end or 0, start + dur)
    return gaps


def overlap_by_name(segments: list, ends: list, lo: int, hi: int) -> dict:
    """Nanoseconds of ``[lo, hi)`` under each name; ``ends`` are the
    stretches' ends (they come in time order)."""
    by = {}
    for name, start, end in segments[bisect.bisect_right(ends, lo):]:
        if start >= hi:
            break
        by[name] = by.get(name, 0) + min(end, hi) - max(start, lo)
    return by


def join(spans: list, modules: list, n: int = 20, floor_ns: int = 1_000_000) -> dict:
    """The report, from the engine thread's spans and device 0's module events.
    ``named_share`` is, of the idle time in gaps over ``floor_ns``, the part
    under a span other than the root."""
    ours = [e for e in spans if e[0].startswith(OURS)]
    segments = innermost_segments(ours)
    ends = [end for _, _, end in segments]
    gaps = device_gaps(modules)
    calls = sorted(s for name, s, _ in spans if name.endswith(".dispatch") or name.startswith("engine.compile"))
    idle_by, rows, long_ns, named_ns = {}, [], 0, 0
    for lo, hi, ended_by in gaps:
        by = overlap_by_name(segments, ends, lo, hi)
        if hi - lo > sum(by.values()):
            by["(no span)"] = hi - lo - sum(by.values())
        for name, ns in by.items():
            idle_by[name] = idle_by.get(name, 0) + ns
        if hi - lo > floor_ns:
            long_ns += hi - lo
            named_ns += sum(ns for name, ns in by.items() if name not in (ROOT_SPAN, "(no span)"))
        label, ns = max(by.items(), key=lambda kv: kv[1])
        called = bisect.bisect_right(calls, hi)  # the call that ended the gap, if the trace holds it
        rows.append({"at_s": lo / 1e9, "gap_s": (hi - lo) / 1e9, "span": label,
                     "span_share": ns / (hi - lo), "before": ended_by,
                     "after_call_us": (hi - calls[called - 1]) / 1e3 if called else None})
    self_by = {}
    for name, start, end in segments:
        self_by[name] = self_by.get(name, 0) + end - start
    per_span, inside = {}, {}
    for name, start, dur in spans:
        row = per_span.setdefault(name, {"count": 0, "total_s": 0.0})
        row["count"] += 1
        row["total_s"] += dur / 1e9
        if not name.startswith(OURS):  # JAX's own span: which phase is it in?
            mine = inside.setdefault(name, {})
            for phase, ns in overlap_by_name(segments, ends, start, start + max(dur, 1)).items():
                mine[phase] = mine.get(phase, 0) + ns
    for name, row in per_span.items():
        if name.startswith(OURS):
            row["self_s"] = self_by.get(name, 0) / 1e9
        else:
            row["inside"] = max(inside[name].items(), key=lambda kv: kv[1])[0] if inside[name] else None
    first = min((e[1] for e in modules), default=0)
    for row in rows:
        row["at_s"] -= first / 1e9
    return {
        "gaps": sorted(rows, key=lambda r: -r["gap_s"])[:n],
        "idle_s_by_span": {k: v / 1e9 for k, v in sorted(idle_by.items(), key=lambda kv: -kv[1])},
        "idle_s": sum(idle_by.values()) / 1e9,
        "idle_s_in_long_gaps": long_ns / 1e9,
        "named_share": named_ns / long_ns if long_ns else None,
        "per_span": dict(sorted(per_span.items(), key=lambda kv: -kv[1]["total_s"])),
        "one_clock": {kind: dispatch_lag(spans, modules, kind, span) for kind, span in PROGRAMS.items()},
    }


def dispatch_lag(spans: list, modules: list, kind: str, span_name: str):
    """Do the k-th module event of ``kind`` and the k-th span it is called in
    fit one clock? The trace may open on modules dispatched before it: ``skip``
    is the least number of leading module events to pass over so that every
    later one starts at or after the start of its span (0-2 on one clock; no
    such number on two). A compile on the served path is a dispatch too."""
    calls = sorted(s for name, s, _ in spans
                   if name == span_name or name.startswith("engine.compile:" + kind[4:]))
    runs = sorted(s for name, s, _ in modules if module_kind(name) == kind)
    if not calls or not runs:
        return None
    for skip in range(len(runs)):
        lags = [r - c for c, r in zip(calls, runs[skip:])]
        if lags and min(lags) >= 0:
            return {"skip": skip, "pairs": len(lags), "min_lag_us": min(lags) / 1e3,
                    "median_lag_us": statistics.median(lags) / 1e3}
    return {"skip": None, "pairs": 0}


def read(path: str, device: str = "TPU"):
    """``(spans, modules)``: every event of the host line that holds the
    ``engine.*`` spans, and device 0's module events."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # reading only: never the chip
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    spans, modules = [], []
    for plane in data.planes:
        on_device = re.search(rf"/device:{device}:0\b", plane.name)
        for line in plane.lines:
            events = [[e.name, int(e.start_ns), int(e.duration_ns)] for e in line.events]
            if on_device and line.name == "XLA Modules":
                modules = events
            elif plane.name.startswith("/host:"):
                if device == "CPU" and line.name.startswith("tf_XLAPjRtCpuClient"):
                    modules += [e for e in events if e[2] > 0]
                ours = sum(1 for e in events if e[0].startswith(OURS))
                if ours > sum(1 for e in spans if e[0].startswith(OURS)):
                    spans = events
    return spans, sorted(modules, key=lambda e: e[1])


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("trace")
    p.add_argument("--device", default="TPU", choices=("TPU", "CPU"))
    p.add_argument("--json", action="store_true", help="print the report as one JSON object")
    args = p.parse_args()
    path = args.trace if os.path.isfile(args.trace) else find_xplane(args.trace)
    if path is None:
        raise SystemExit(f"no .xplane.pb under {args.trace}")
    spans, modules = read(path, args.device)
    report = join(spans, modules)
    report["xplane"] = {"path": path, "bytes": os.path.getsize(path),
                        "host_events": len(spans), "module_events": len(modules)}
    if args.json:
        print(json.dumps(report))
        return 0
    print(f"{path}: {report['xplane']['bytes']} B, {len(spans)} events on the engine thread, "
          f"{len(modules)} module events on device 0")
    print(f"\n(a) the longest of the device's gaps ({report['idle_s']:.4f} s idle in all, "
          f"{report['idle_s_in_long_gaps']:.4f} s in gaps over 1 ms, of which under a span other "
          f"than {ROOT_SPAN}: {100 * (report['named_share'] or 0):.1f} %)")
    for row in report["gaps"]:
        print(f"  +{row['at_s']:8.4f} s  {1e3 * row['gap_s']:8.3f} ms  {row['span']:<24} "
              f"{100 * row['span_share']:5.1f} %  before {row['before']}"
              + (f", {row['after_call_us']:.0f} us after the call" if row["after_call_us"] is not None else ""))
    print("\n(b) idle seconds by innermost span")
    for name, s in report["idle_s_by_span"].items():
        print(f"  {name:<28} {s:9.5f}")
    print("\n(c) spans on the engine thread: count, total s, self s (or the phase it sits in)")
    for name, row in report["per_span"].items():
        tail = f"{row['self_s']:9.5f}" if "self_s" in row else f"in {row['inside']}"
        print(f"  {name[:48]:<48} {row['count']:7d} {row['total_s']:9.5f} {tail}")
    print("\n(d) one clock: module event k against dispatch span k")
    for kind, lag in report["one_clock"].items():
        print(f"  {kind}: {lag}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
