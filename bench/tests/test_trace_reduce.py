"""``trace_reduce`` on a trace recorded on the CPU (``data/``) and on a
small trace written by hand in the layout of a TPU's."""
import os

import pytest
from jax.profiler import ProfileData

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "cpu_trace.xplane.pb")
CPU = ("/host:CPU", "tf_XLAPjRtCpuClient")


def _plane(pid, name, lines):
    """An XPlane text proto; ``lines``: {line name: [(name, start_ns,
    dur_ns)]}, with every event name as its own metadata."""
    names = sorted({e[0] for evs in lines.values() for e in evs})
    meta = {n: i + 1 for i, n in enumerate(names)}
    out = [f"planes {{ id: {pid} name: '{name}'"]
    for lid, (lname, evs) in enumerate(lines.items()):
        out.append(f"  lines {{ id: {lid} name: '{lname}' timestamp_ns: 0")
        for n, start, dur in evs:
            out.append(f"    events {{ metadata_id: {meta[n]} "
                       f"offset_ps: {start * 1000} duration_ps: {dur * 1000} }}")
        out.append("  }")
    for n, i in meta.items():
        out.append(f"  event_metadata {{ key: {i} value {{ id: {i} name: '{n}' }} }}")
    out.append("}")
    return "\n".join(out)


def synthetic():
    """Window [100, 1100] ns. Device 0: ops [50, 300] (clipped to 100),
    [200, 400] overlapping it, all-gather [600, 700]; device 1: one op
    [100, 1100]. Host: ledger span [400, 650], call span [100, 1100]."""
    host = _plane(1, "/host:CPU", {"python": [
        ("bench.window", 100, 1000), ("bench.call", 100, 1000),
        ("bench.ledger", 400, 250)]})
    dev0 = _plane(2, "/device:TPU:0", {"XLA Ops": [
        ("fusion.1", 50, 250), ("fusion.2", 200, 200),
        ("all-gather.3", 600, 100)], "XLA Modules": [("jit_run", 0, 2000)]})
    dev1 = _plane(3, "/device:TPU:1", {"XLA Ops": [("fusion.1", 100, 1000)]})
    text = "\n".join([host, dev0, dev1])
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


def test_synthetic_busy_union_idle_collective_and_labels():
    r = tr.reduce(synthetic())
    assert r["n_devices"] == 2
    assert r["window_s"] == pytest.approx(1000e-9)
    # device 0: union of [100,300] and [200,400] is [100,400]; plus
    # [600,700] -> 400 ns busy. Device 1: 1000 ns busy.
    assert r["busy_by_device"]["/device:TPU:0"] == pytest.approx(400e-9)
    assert r["busy_by_device"]["/device:TPU:1"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(700e-9)
    assert r["idle_share"] == pytest.approx(0.3)
    assert r["collective_s"] == pytest.approx(50e-9)   # 100 ns on one of 2
    # device 0's gaps: [400,600] (middle 500: in the ledger span) and
    # [700,1100] (middle 900: only the call span); means over 2 devices
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert gaps == pytest.approx({"call": 200e-9, "ledger": 100e-9})
    ops = dict(map(tuple, r["device_ops"]))
    assert ops["fusion.1"] == pytest.approx((200 + 1000) * 1e-9 / 2)
    assert list(ops)[0] == "fusion.1"     # ranked by time


def test_union_merges_overlaps_and_touching():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]
    assert tr.gaps([(0, 4), (5, 7)], 0, 10) == [(4, 5), (7, 10)]


def test_no_window_span_is_an_error():
    text = _plane(2, "/device:TPU:0", {"XLA Ops": [("fusion.1", 0, 10)]})
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))
    with pytest.raises(ValueError):
        tr.reduce(pd)


def test_recorded_cpu_trace():
    pd = tr.load(DATA)
    spans = tr.host_spans(pd)
    assert sorted({s[2] for s in spans}) == ["call", "ledger", "window"]
    r = tr.reduce(pd, selector=CPU)
    assert r["n_devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["idle_share"] == pytest.approx(1 - r["busy_s"] / r["window_s"])
    assert r["collective_s"] == 0
    ops = dict(map(tuple, r["device_ops"]))
    assert max(ops, key=ops.get) == "dot_general.1"
    # the host sleeps 2 ms in each ledger span while the CPU runs nothing
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert gaps["ledger"] > 3 * 2e-3 * 0.9
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_tpu_selector_finds_no_device_on_a_cpu_trace():
    assert tr.reduce(tr.load(DATA))["n_devices"] == 0
