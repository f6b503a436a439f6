"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy time and idle share over a window, device
time per compiled program and per operation (kernels among them), host
span time by name, and the longest device idle gaps with the host span
that was open in each.

A device is a plane named ``/device:<kind>:<n>``. Its operations are the
events on the line named ``XLA Ops`` and its programs those on the line
``XLA Modules``; a program's name drops the ``(<id>)`` that XLA appends.
Host spans are the events of every other plane whose line is a host
thread. The window is the host span ``window`` (by default the
benchmark's ``bench/window``), and everything is clipped to it.
"""
from __future__ import annotations

import collections
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench/window"
_PROGRAM_ID = re.compile(r"\(\d+\)$")


def find_xplane(log_dir: str) -> str:
    """The one ``.xplane.pb`` a ``jax.profiler.trace(log_dir)`` wrote."""
    got = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                    recursive=True)
    if len(got) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(got)}")
    return got[0]


def _events(path: str) -> tuple[dict, dict]:
    """({device plane: {line: [(name, start, end)]}}, {host span name:
    [(start, end)]}), times in ns."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: dict = {}
    host: dict = collections.defaultdict(list)
    for plane in data.planes:
        is_device = plane.name.startswith("/device:") \
            and "CPU" not in plane.name
        lines: dict = collections.defaultdict(list)
        for line in plane.lines:
            for ev in line.events:
                item = (ev.name, int(ev.start_ns),
                        int(ev.start_ns + ev.duration_ns))
                if is_device:
                    lines[line.name].append(item)
                elif plane.name.startswith("/host:"):
                    host[ev.name].append(item[1:])
        if is_device and lines:
            devices[plane.name] = dict(lines)
    return devices, dict(host)


def union(intervals: list) -> list:
    """Merged, sorted, non-overlapping (start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def _named(span: str) -> bool:
    """A span of the program or the benchmark (``layer/what``), not one
    of the runtime's own or of the Python tracer."""
    return "/" in span and not span.startswith("$")


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def reduce(path: str, window: str = WINDOW_SPAN, top: int = 10) -> dict:
    """Seconds, averaged over the device planes:

    ``window_s``, ``busy_s`` (union of operation intervals),
    ``idle_share`` (1 - busy / window), ``programs`` and ``ops`` (device
    seconds by name, summed), ``op_counts`` (events by name), ``host``
    (seconds by span name, summed over the window), ``idle_gaps`` (the
    ``top`` longest gaps on the first device, each named by the shortest
    ``layer/what`` host span that covers its middle) and ``devices``
    (their count)."""
    devices, host = _events(path)
    if not devices:
        raise ValueError(f"{path} holds no device plane")
    spans = host.get(window)
    if not spans:
        raise ValueError(f"{path} holds no host span {window!r}")
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    n_dev = len(devices)
    busy = 0
    programs: dict = collections.Counter()
    ops: dict = collections.Counter()
    counts: dict = collections.Counter()
    gaps: list = []
    for i, (_, lines) in enumerate(sorted(devices.items())):
        clipped = []
        for name, s, e in lines.get(OPS_LINE, []):
            s, e = _clip(s, e, lo, hi)
            if e > s:
                clipped.append((s, e))
                ops[name] += (e - s) / n_dev
                counts[name] += 1
        merged = union(clipped)
        busy += sum(e - s for s, e in merged)
        for name, s, e in lines.get(MODULES_LINE, []):
            s, e = _clip(s, e, lo, hi)
            if e > s:
                programs[_PROGRAM_ID.sub("", name)] += (e - s) / n_dev
        if i == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = [(edges[j], edges[j + 1])
                    for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
    host_s = collections.Counter()
    for name, ivs in host.items():
        for s, e in ivs:
            s, e = _clip(s, e, lo, hi)
            if e > s:
                host_s[name] += (e - s) / 1e9
    named_gaps = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) // 2
        cover = [(ee - ss, name) for name, ivs in host.items()
                 if _named(name) and name != window
                 for ss, ee in ivs if ss <= mid <= ee]
        named_gaps.append((min(cover)[1] if cover else "no host span",
                           (e - s) / 1e9))
    window_s = (hi - lo) / 1e9
    busy_s = busy / n_dev / 1e9
    return {"window_s": window_s, "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / window_s,
            "programs": {k: v / 1e9 for k, v in programs.items()},
            "ops": {k: v / 1e9 for k, v in ops.items()},
            "op_counts": dict(counts), "host": dict(host_s),
            "idle_gaps": named_gaps, "devices": n_dev}


def breakdown(red: dict, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device operations that took
    most time, and the longest idle gaps by what the host was doing."""
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in red["idle_gaps"][:top]]}
