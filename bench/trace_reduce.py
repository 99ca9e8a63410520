"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

Device ops are the events of the op line of each device plane (on a TPU:
planes ``/device:TPU:<n>``, line ``XLA Ops``). Host spans are the
benchmark's ``bench.<name>`` annotations, wherever they lie. The traced
window is the ``bench.window`` span; every interval is clipped to it.

- busy: the union of a device's op intervals (overlapping ops count once);
- idle share: 1 - mean busy over the devices / window;
- collective time: the union of a device's all-gather, all-reduce,
  collective-permute, reduce-scatter and all-to-all ops;
- top ops: device seconds per op name, mean over the devices;
- idle gaps: each stretch in which a device runs nothing, labelled by the
  innermost host span open at its middle, summed per label and averaged
  over the devices.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

from spans import PREFIX

TPU = ("/device:TPU:", "XLA Ops")
COLLECTIVE = re.compile(r"all-gather|all-reduce|collective-permute|"
                        r"reduce-scatter|all-to-all", re.IGNORECASE)


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _events(line):
    for ev in line.events:
        yield ev.start_ns, ev.start_ns + ev.duration_ns, ev.name


def device_ops(pd, selector=TPU):
    """{plane name: [(start_ns, end_ns, op name)]} of the device planes."""
    plane_prefix, op_line = selector
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith(plane_prefix):
            continue
        ops = [ev for line in plane.lines if line.name.startswith(op_line)
               for ev in _events(line)]
        if ops:
            out[plane.name] = ops
    return out


def host_spans(pd):
    """[(start_ns, end_ns, name)] of the benchmark's host spans."""
    return [(a, b, name[len(PREFIX):]) for plane in pd.planes
            for line in plane.lines for a, b, name in _events(line)
            if name.startswith(PREFIX)]


def union(intervals):
    """Sorted, merged copy of ``[(start, end, ...)]`` as [(start, end)]."""
    out = []
    for a, b, *_ in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(iv) for iv in out]


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi), *rest) for a, b, *rest in intervals
            if b > lo and a < hi]


def length(intervals):
    return sum(b - a for a, b in intervals)


def gaps(busy, lo, hi):
    """The stretches of [lo, hi] that the merged ``busy`` leaves free."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def labelled(stretches, spans):
    """Each (start, end) stretch, in time order, with the name of the
    innermost span open at its middle (the latest-started one)."""
    spans = sorted(spans)
    active, i, out = [], 0, []
    for a, b in stretches:
        t = (a + b) / 2
        while i < len(spans) and spans[i][0] <= t:
            active.append(spans[i])
            i += 1
        active = [s for s in active if s[1] > t]
        out.append((a, b, max(active)[2] if active else "no span"))
    return out


def reduce(pd, selector=TPU, top=10):
    spans = host_spans(pd)
    windows = [(a, b) for a, b, name in spans if name == "window"]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    lo, hi = min(a for a, _ in windows), max(b for _, b in windows)
    devices = device_ops(pd, selector)
    n = len(devices)
    if not n:
        return {"window_s": (hi - lo) * 1e-9, "n_devices": 0}
    busy_by, coll_by = {}, {}
    per_op = defaultdict(float)
    per_gap = defaultdict(float)
    inner = [s for s in spans if s[2] != "window"]
    for plane, ops in devices.items():
        ops = clip(ops, lo, hi)
        busy = union(ops)
        busy_by[plane] = length(busy) * 1e-9
        coll_by[plane] = length(union(
            [o for o in ops if COLLECTIVE.search(o[2])])) * 1e-9
        for a, b, name in ops:
            per_op[name] += (b - a) * 1e-9 / n
        for a, b, name in labelled(gaps(busy, lo, hi), inner):
            per_gap[name] += (b - a) * 1e-9 / n
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy_by.values()) / n

    def ranked(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                ][:top]

    return {"window_s": window_s, "n_devices": n, "busy_s": busy_s,
            "busy_by_device": busy_by, "idle_share": 1.0 - busy_s / window_s,
            "collective_s": sum(coll_by.values()) / n,
            "device_ops": ranked(per_op), "idle_gaps": ranked(per_gap)}
