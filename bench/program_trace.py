"""The program's own names in a ``--trace 1`` run's profiler trace.

``bench/run.py`` writes the trace; ``trace_reduce`` reduces it with the
benchmark's spans. This module reads what the engine names itself
(``repro/core/telemetry.py``), within the ``bench.window`` span:

- host spans ``blade.<phase>``: per name, the time covered (``total_s``),
  the self time (``self_s``: less the time of the spans nested in it) and
  the sum of each count the spans carry (rounds, hashes, rows, bytes, ...;
  not the ``round`` or ``client`` a span names);
- stages: per stage scope (``local_train`` ... ``finalize``), the union of
  the device intervals of the ops whose scope path holds the stage, so a
  ``while`` container and its body ops count once; ``staged_s`` is the
  union over all stages, ``unstaged_ops`` the device time of the ops
  outside every stage, less what staged ops cover of it;
- idle: each stretch in which the device runs nothing, labelled by the
  innermost ``blade.*`` span open at its middle (``no program span``).

Device seconds are means over the device planes. The scope path of an op
is its ``tf_op`` stat (``jit(run)/while/body/closed_call/local_train/...:``
on a TPU v5e): the op's event metadata carries it, and ``ProfileData``
shows event stats only, so the device planes' event metadata is read from
the ``.xplane.pb`` itself (``op_scopes``).

    python3 bench/program_trace.py <trace dir>

prints the whole table of a trace directory.
"""
from __future__ import annotations

import bisect
import json
import os
import struct
import sys
from collections import defaultdict

import trace_reduce as tr

# the program's names (repro.core.telemetry), copied: the benchmark also
# reads traces of programs that predate them, and imports nothing of them
PREFIX = "blade."
STAGES = ("local_train", "perturb", "attack", "communicate", "mine",
          "finalize")
# host phases of the drivers themselves, as against the layers they call
DRIVER = ("blade.plan", "blade.init", "blade.dispatch", "blade.history",
          "blade.cohort")
NO_SPAN = "no program span"
# span args that name a round or a client rather than count something
IDS = ("round", "client")
SCOPE_STAT = "tf_op"
TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".bench_trace")


# -- the event metadata of an .xplane.pb (protobuf wire format) -----------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of a message in ``buf`` (a memoryview): ints
    for varints and fixed widths, memoryviews for length-delimited
    fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        elif kind == 1:
            val = struct.unpack_from("<Q", buf, i)[0]
            i += 8
        elif kind == 2:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        elif kind == 5:
            val = struct.unpack_from("<I", buf, i)[0]
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, val


def _map_value(entry):
    """The value message of a protobuf map entry (key 1, value 2)."""
    return next((v for f, v in _fields(entry) if f == 2), memoryview(b""))


def _str(v):
    return bytes(v).decode(errors="replace")


def op_scopes(path, selector=tr.TPU):
    """{device plane: {op name: scope path}}: the ``tf_op`` stat of each
    op's event metadata in the device planes of the trace at ``path``.
    (XSpace: planes 1; XPlane: name 2, event_metadata 4, stat_metadata 5;
    XEventMetadata: name 2, stats 5; XStat: metadata_id 1, str_value 5.)"""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        fields = list(_fields(plane)) if field == 1 else []
        name = next((_str(v) for f, v in fields if f == 2), "")
        if not name.startswith(selector[0]):
            continue
        stat_ids = [dict(_fields(_map_value(v))) for f, v in fields if f == 5]
        scope_id = next((m.get(1, 0) for m in stat_ids
                         if _str(m.get(2, b"")) == SCOPE_STAT), None)
        scopes = out.setdefault(name, {})
        for meta in (_map_value(v) for f, v in fields if f == 4):
            op, scope = "", None
            for f, v in _fields(meta):
                if f == 2:
                    op = _str(v)
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat.get(1, 0) == scope_id and 5 in stat:
                        scope = _str(stat[5])
            if scope is not None:
                scopes[op] = scope
    return out


def stages_of(scope):
    """The stages named in a scope path: its ``/``-separated parts, the
    last one less the ``:<op type>`` a TPU's ``tf_op`` ends with."""
    parts = scope.split("/")
    parts[-1] = parts[-1].split(":")[0]
    return [s for s in STAGES if s in parts]


# -- the reduction ---------------------------------------------------------

def program_spans(pd):
    """{line key: [(start_ns, end_ns, name, {count: value})]} of the
    ``blade.*`` host spans, one list per host thread."""
    out = {}
    for plane in pd.planes:
        for line in plane.lines:
            evs = [(ev.start_ns, ev.end_ns, ev.name,
                    {k: v for k, v in ev.stats
                     if isinstance(v, int) and k not in IDS})
                   for ev in line.events if ev.name.startswith(PREFIX)]
            if evs:
                out[(plane.name, line.name)] = evs
    return out


def self_times(spans):
    """[(name, self ns)] of time-ordered, properly nested spans of one
    thread: each span's length less the length of its direct children."""
    out, stack = [], []      # stack: [end, name, length, children]

    def close(entry):
        out.append((entry[1], entry[2] - entry[3]))

    for a, b, name, _ in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][0] <= a:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(b, stack[-1][0]) - a
        stack.append([b, name, b - a, 0])
    while stack:
        close(stack.pop())
    return out


def uncovered(a, b, merged, starts):
    """Length of [a, b] not covered by the sorted, merged intervals
    ``merged`` (``starts``: their starts)."""
    covered = 0
    j = max(bisect.bisect_right(starts, a) - 1, 0)
    while j < len(merged) and merged[j][0] < b:
        covered += max(min(b, merged[j][1]) - max(a, merged[j][0]), 0)
        j += 1
    return (b - a) - covered


def reduce(pd, scopes, selector=tr.TPU, top=10):
    """The program's spans, stages and labelled idle within the window:
    the ``bench.window`` span, else (a trace an operator took with
    ``launch/train --trace-dir``) the extent of the ``blade.run`` spans.
    ``scopes``: ``op_scopes`` of the same trace."""
    threads = program_spans(pd)
    windows = [(a, b) for a, b, name in tr.host_spans(pd) if name == "window"]
    if not windows:
        windows = [(a, b) for v in threads.values() for a, b, name, _ in v
                   if name == PREFIX + "run"]
    if not windows:
        raise ValueError("the trace holds no bench.window or blade.run span")
    lo, hi = min(a for a, _ in windows), max(b for _, b in windows)
    threads = {k: tr.clip(v, lo, hi) for k, v in threads.items()}
    spans = [s for v in threads.values() for s in v]
    by_name = defaultdict(list)
    counts = defaultdict(lambda: defaultdict(int))
    for a, b, name, stats in spans:
        by_name[name].append((a, b))
        for k, v in stats.items():
            counts[name][k] += v
    self_ns = defaultdict(int)
    for v in threads.values():
        for name, ns in self_times(v):
            self_ns[name] += ns
    span_table = {name: {"total_s": tr.length(tr.union(ivs)) * 1e-9,
                         "self_s": self_ns[name] * 1e-9, "count": len(ivs),
                         **dict(counts[name])}
                  for name, ivs in sorted(by_name.items())}

    devices = tr.device_ops(pd, selector)
    n = len(devices)
    stage_s = defaultdict(float)
    staged_s = busy_s = 0.0
    unstaged = defaultdict(float)
    idle = defaultdict(float)
    labels = [(a, b, name) for a, b, name, _ in spans]
    for plane, ops in devices.items():
        ops = tr.clip(ops, lo, hi)
        scope_of = scopes.get(plane, {})
        stages = {name: stages_of(scope_of.get(name, ""))
                  for name in {op[2] for op in ops}}
        per_stage = defaultdict(list)
        for op in ops:
            for stage in stages[op[2]]:
                per_stage[stage].append(op)
        for stage, stage_ops in per_stage.items():
            stage_s[stage] += tr.length(tr.union(stage_ops)) * 1e-9 / n
        staged = tr.union([op for v in per_stage.values() for op in v])
        staged_s += tr.length(staged) * 1e-9 / n
        busy = tr.union(ops)
        busy_s += tr.length(busy) * 1e-9 / n
        starts = [a for a, _ in staged]
        for a, b, name in ops:
            if not stages[name]:
                unstaged[name] += uncovered(a, b, staged, starts) * 1e-9 / n
        for a, b, name in tr.labelled(tr.gaps(busy, lo, hi), labels):
            idle[NO_SPAN if name == "no span" else name] += (b - a) * 1e-9 / n

    def ranked(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                if v > 0][:top]

    return {"window_s": (hi - lo) * 1e-9, "n_devices": n, "busy_s": busy_s,
            "spans": span_table, "stages": dict(stage_s),
            "staged_s": staged_s, "unstaged_ops": ranked(unstaged),
            "idle": dict(idle)}


def read(run):
    """The reduction of a traced run's trace, cached on ``run``; None when
    the run was not traced."""
    if not getattr(run, "trace", None):
        return None
    if getattr(run, "program_trace", None) is None:
        path = tr.find_xplane(TRACE_DIR)
        run.program_trace = reduce(tr.load(path), op_scopes(path))
    return run.program_trace


def per_round_ms(run, seconds):
    """``seconds`` of the traced window in ms per traced round; None when
    nothing was read."""
    if not seconds or not run.rounds_traced:
        return None
    return 1e3 * seconds / run.rounds_traced


def stage_ms_per_round(run, stage):
    got = read(run)
    if not got or not got["n_devices"]:
        return None
    return per_round_ms(run, got["stages"].get(stage))


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        raise SystemExit("usage: python3 bench/program_trace.py <trace dir>")
    path = tr.find_xplane(argv[0])
    print(json.dumps(reduce(tr.load(path), op_scopes(path)), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
