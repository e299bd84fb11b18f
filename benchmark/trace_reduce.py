"""From a ``jax.profiler`` trace to the numbers the per-layer metrics read.

Two steps, so that the second can be tested on a small recorded file:

  read_xplane(path)  -> the reduced trace: per device the module events, and
                        on device 0 the op events, as [name, start_ns, dur_ns]
  busy_union_ns, idle_share, module_medians_ms, top_ops, idle_gaps
                     -> arithmetic on the reduced trace

The xplane reading follows ``chip_smoke.py``'s ``host_clock_vs_trace``
(PR 21): planes whose name holds "TPU", the lines "XLA Modules" and
"XLA Ops". Only the process that held the chip could record the trace; this
file reads it afterwards, on the CPU, with nothing but JAX.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import statistics

MODULE_LINE, OP_LINE = "XLA Modules", "XLA Ops"


def short_op_name(name: str, limit: int = 96) -> str:
    """An op event is named by its whole HLO line: keep the name, the result
    type without layouts, and the op, cut to ``limit`` characters."""
    return re.sub(r"\{[^{}]*\}", "", name)[:limit]


def find_xplane(trace_dir: str):
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    return paths[-1] if paths else None


def read_xplane(path: str, device_marker: str = "TPU") -> dict:
    """Reduced trace of one ``.xplane.pb``. Times are nanoseconds on the
    trace's own clock."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # reading only: never the chip
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, seen = {}, {}
    for plane in data.planes:
        seen[plane.name] = [line.name for line in plane.lines]
        m = re.search(rf"{device_marker}:(\d+)", plane.name)
        if not m or "/device:" not in plane.name:
            continue
        dev = {"modules": [], "ops": []}
        for line in plane.lines:
            if line.name == MODULE_LINE:
                dev["modules"] = [
                    [e.name, int(e.start_ns), int(e.duration_ns)] for e in line.events]
            elif line.name == OP_LINE and m.group(1) == "0":
                dev["ops"] = [
                    [short_op_name(e.name), int(e.start_ns), int(e.duration_ns)]
                    for e in line.events]
        dev["modules"].sort(key=lambda e: e[1])  # by start
        dev["ops"].sort(key=lambda e: e[1])
        devices[m.group(1)] = dev
    return {"devices": devices, "planes_seen": seen}


def save_reduced(reduced: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(reduced, f, separators=(",", ":"))


def load_reduced(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def module_kind(name: str) -> str:
    """``jit_decode(123456789)`` -> ``jit_decode``."""
    return name.split("(", 1)[0]


def span_ns(reduced: dict):
    """[first start, last end] over the module events of every device."""
    starts = [e[1] for d in reduced["devices"].values() for e in d["modules"]]
    ends = [e[1] + e[2] for d in reduced["devices"].values() for e in d["modules"]]
    return (min(starts), max(ends)) if starts else None


def busy_union_ns(events: list) -> int:
    """Length of the union of the events' intervals."""
    total, cur_s, cur_e = 0, None, None
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if cur_e is None or start > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = start, start + dur
        else:
            cur_e = max(cur_e, start + dur)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_seconds(reduced: dict) -> float:
    """Seconds in which a module ran, averaged over the devices traced."""
    devs = [d for d in reduced["devices"].values() if d["modules"]]
    if not devs:
        return 0.0
    return sum(busy_union_ns(d["modules"]) for d in devs) / len(devs) / 1e9


def idle_share(reduced: dict, device: str = "0"):
    """1 - busy union of device ``device`` over the traced span, in [0, 1]."""
    span = span_ns(reduced)
    dev = reduced["devices"].get(device)
    if not span or not dev or not dev["modules"]:
        return None
    return 1.0 - busy_union_ns(dev["modules"]) / (span[1] - span[0])


def module_medians_ms(reduced: dict, device: str = "0") -> dict:
    """Per kind of module: median device duration in ms, count, total ms."""
    by_kind = {}
    for name, _, dur in reduced["devices"].get(device, {}).get("modules", ()):
        by_kind.setdefault(module_kind(name), []).append(dur / 1e6)
    return {k: {"median_ms": statistics.median(v), "count": len(v), "total_ms": sum(v)}
            for k, v in by_kind.items()}


def top_ops(reduced: dict, n: int = 10, device: str = "0") -> list:
    """The ``n`` device operations with most time: [[name, seconds], ...]."""
    total = {}
    for name, _, dur in reduced["devices"].get(device, {}).get("ops", ()):
        total[name] = total.get(name, 0) + dur
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in top]


def idle_gaps(reduced: dict, n: int = 5, device: str = "0") -> list:
    """The ``n`` longest gaps between module events on device ``device``,
    each labelled by the module that ended it (what the host was doing in
    the gap needs annotations inside the program)."""
    mods = reduced["devices"].get(device, {}).get("modules", ())
    gaps, end = [], None
    for name, start, dur in mods:
        if end is not None and start > end:
            gaps.append([f"before {module_kind(name)}", (start - end) / 1e9])
        end = max(end or 0, start + dur)
    return sorted(gaps, key=lambda g: -g[1])[:n]
