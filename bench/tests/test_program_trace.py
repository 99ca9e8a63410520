"""``program_trace`` and its metrics on a small trace written by hand in
the layout of a TPU v5e's: op events named by their HLO text, whose event
metadata carries the op's scope path in a ``tf_op`` stat ending ``:``,
beside ``XLA Modules`` and ``Async XLA Ops`` lines; and the program's
``blade.*`` spans, with their counts, on a host thread."""
import types

import pytest
from jax.profiler import ProfileData

import program_trace as pt
import trace_reduce as tr
from metrics import (driver_idle_ms_per_round, driver_ms_per_round,
                     eval_ms_per_round, mine_ms_per_round, mix_ms_per_round,
                     train_ms_per_round)

SCAN = "jit(run)/while/body/closed_call"
SCOPES = {
    "while.1": "jit(run)/while",
    "while.2": f"{SCAN}/local_train/while",
    "fusion.1": f"{SCAN}/local_train/while/body/closed_call/dot_general",
    "fusion.2": f"{SCAN}/communicate/reduce_sum",
    "fusion.3": f"{SCAN}/mine/while/body/xor",
    "fusion.4": f"{SCAN}/finalize/closed_call/log_softmax",
    "copy.5": f"{SCAN}/dynamic_update_slice",
    "copy.7": "jit(run)/copy",
    "copy.8": "jit(run)/copy",
}
# device ops (name, start ns, duration ns): the scan's container while.1
# holds local training (its loop while.2 and two runs of its body op
# fusion.1), then communicate, mine and finalize, then an unscoped copy
OPS = [("while.1", 160, 440), ("while.2", 170, 230), ("fusion.1", 180, 70),
       ("fusion.1", 300, 80), ("fusion.2", 400, 50), ("fusion.3", 450, 70),
       ("fusion.4", 520, 60), ("copy.5", 580, 20), ("copy.7", 650, 10),
       ("copy.8", 880, 15)]
# host spans (name, start, duration, counts) in one call of the window
SPANS = [("bench.window", 0, 1000, {}), ("bench.call", 0, 1000, {}),
         ("bench.engine", 5, 895, {}),
         ("blade.run", 10, 880, {"rounds": 2, "clients": 4, "hashes": 80}),
         ("blade.plan", 20, 40, {}), ("blade.init", 60, 40, {}),
         ("blade.dispatch", 100, 50, {}), ("blade.fetch", 150, 550,
                                           {"bytes": 64}),
         ("blade.history", 700, 20, {}), ("blade.ledger", 720, 80,
                                          {"blocks": 2}),
         ("blade.ledger.validate", 760, 30, {"blocks": 2})]


def _event_plane(pid, name, lines, meta_stats=None, stat_names=()):
    """An XPlane text proto; ``lines``: {line name: [(event, start_ns,
    dur_ns, {stat: int})]}; ``meta_stats``: {event: 'stats {...}'}."""
    events = sorted({e[0] for evs in lines.values() for e in evs})
    meta = {n: i + 1 for i, n in enumerate(events)}
    stats = {k: i + 1 for i, k in enumerate(sorted(
        {k for evs in lines.values() for e in evs for k in e[3]}
        | set(stat_names)))}
    out = [f"planes {{ id: {pid} name: '{name}'"]
    for lid, (lname, evs) in enumerate(lines.items()):
        out.append(f"  lines {{ id: {lid} name: '{lname}' timestamp_ns: 0")
        for n, start, dur, args in evs:
            st = " ".join(f"stats {{ metadata_id: {stats[k]} int64_value: "
                          f"{v} }}" for k, v in args.items())
            out.append(f"    events {{ metadata_id: {meta[n]} offset_ps: "
                       f"{start * 1000} duration_ps: {dur * 1000} {st} }}")
        out.append("  }")
    for n, i in meta.items():
        extra = (meta_stats or {}).get(n, "").format(**stats)
        out.append(f"  event_metadata {{ key: {i} value {{ id: {i} "
                   f"name: '{n}' {extra} }} }}")
    out += [f"  stat_metadata {{ key: {i} value {{ id: {i} name: '{k}' }} }}"
            for k, i in stats.items()]
    out.append("}")
    return "\n".join(out)


def hlo(op):
    """An op event's name on a TPU: the instruction's HLO text."""
    return f"%{op} = f32[4]{{0:T(128)}} {op.split('.')[0]}(f32[4]{{0}} %p)"


def xspace(spans=SPANS, ops=OPS):
    """Serialized XSpace: a host plane with ``spans`` on one thread and a
    TPU plane whose op events carry their scope in event metadata."""
    host = _event_plane(1, "/host:CPU", {"python3": spans})
    scoped = {hlo(n): f"display_name: '{n}' stats {{{{ metadata_id: "
                      f"{{hlo_category}} str_value: 'loop fusion' }}}} "
                      f"stats {{{{ metadata_id: {{tf_op}} str_value: "
                      f"'{SCOPES[n]}:' }}}}" for n, *_ in ops}
    dev = _event_plane(2, "/device:TPU:0", {
        "XLA Modules": [("jit_run(7)", 150, 750, {})],
        "XLA Ops": [(hlo(n), start, dur, {}) for n, start, dur in ops],
        "Async XLA Ops": [(hlo("copy.7"), 0, 1000, {})]},
        scoped, ["hlo_category", "tf_op"])
    return ProfileData.text_proto_to_serialized_xspace(host + "\n" + dev)


@pytest.fixture
def trace(tmp_path):
    path = tmp_path / "plugins" / "profile" / "t" / "h.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(xspace())
    return tmp_path, str(path)


def reduced(path):
    return pt.reduce(tr.load(path), pt.op_scopes(path))


def test_scopes_come_from_event_metadata(trace):
    _, path = trace
    assert pt.op_scopes(path) == {"/device:TPU:0": {
        hlo(n): f"{scope}:" for n, scope in SCOPES.items()}}
    assert pt.stages_of("jit(run)/while/body/closed_call/mine:") == ["mine"]


def reduced_stages():
    # local_train: while.2 [170, 400] holds both runs of fusion.1
    return {"local_train": 230e-9, "communicate": 50e-9, "mine": 70e-9,
            "finalize": 60e-9}


def test_stage_unions_count_container_and_body_once(trace):
    r = reduced(trace[1])
    assert r["stages"] == pytest.approx(reduced_stages())
    # busy: [160, 600] + [650, 660] + [880, 895]
    assert r["busy_s"] == pytest.approx(465e-9)
    assert sum(r["stages"].values()) <= r["busy_s"]
    assert r["staged_s"] == pytest.approx(410e-9)
    # what staged ops leave of while.1 ([160, 170] and [580, 600]) and of
    # the unscoped copies
    assert dict(map(tuple, r["unstaged_ops"])) == pytest.approx({
        hlo("while.1"): 30e-9, hlo("copy.5"): 20e-9, hlo("copy.8"): 15e-9,
        hlo("copy.7"): 10e-9})


def test_idle_is_labelled_by_the_innermost_program_span(trace):
    r = reduced(trace[1])
    # gaps [0,160] (middle in init), [600,650] (fetch), [660,880] (middle
    # 770 in ledger.validate, inside ledger) and [895,1000] (after run)
    assert r["idle"] == pytest.approx({
        "blade.init": 160e-9, "blade.fetch": 50e-9,
        "blade.ledger.validate": 220e-9, pt.NO_SPAN: 105e-9})
    assert sum(r["idle"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_self_time_excludes_nested_spans_and_counts_sum(trace):
    spans = reduced(trace[1])["spans"]
    assert spans["blade.run"]["total_s"] == pytest.approx(880e-9)
    # 880 less plan 40, init 40, dispatch 50, fetch 550, history 20,
    # ledger 80
    assert spans["blade.run"]["self_s"] == pytest.approx(100e-9)
    assert spans["blade.ledger"]["self_s"] == pytest.approx(50e-9)
    assert spans["blade.ledger.validate"]["self_s"] == pytest.approx(30e-9)
    assert spans["blade.run"]["hashes"] == 80
    assert spans["blade.fetch"]["bytes"] == 64
    assert spans["blade.run"]["count"] == 1
    assert not any(name.startswith("bench.") for name in spans)


def test_metrics_read_their_values(trace, monkeypatch):
    trace_dir, _ = trace
    monkeypatch.setattr(pt, "TRACE_DIR", str(trace_dir))
    run = types.SimpleNamespace(trace={"n_devices": 1}, rounds_traced=2)
    per_round = {train_ms_per_round: 230, mix_ms_per_round: 50,
                 mine_ms_per_round: 70, eval_ms_per_round: 60,
                 # plan 40 + init 40 + dispatch 50 + history 20 (self)
                 driver_ms_per_round: 150,
                 # idle under init 160 + fetch 50
                 driver_idle_ms_per_round: 210}
    for metric, ns in per_round.items():
        assert metric.read(run) == pytest.approx(ns * 1e-6 / 2), metric


def test_metrics_read_nothing_without_a_trace_or_program_names(
        tmp_path, monkeypatch):
    untraced = types.SimpleNamespace(trace=None, rounds_traced=0)
    # the parent program: the benchmark's spans only, ops without stages
    bare = [s for s in SPANS if s[0].startswith("bench.")]
    path = tmp_path / "plugins" / "profile" / "t" / "h.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(xspace(spans=bare, ops=[("copy.7", 650, 10)]))
    monkeypatch.setattr(pt, "TRACE_DIR", str(tmp_path))
    traced = types.SimpleNamespace(trace={"n_devices": 1}, rounds_traced=2)
    for metric in (train_ms_per_round, mix_ms_per_round, mine_ms_per_round,
                   eval_ms_per_round, driver_ms_per_round,
                   driver_idle_ms_per_round):
        assert metric.read(untraced) is None
        assert metric.read(traced) is None


def test_trace_reduce_ignores_program_spans():
    with_program = tr.reduce(ProfileData.from_serialized_xspace(xspace()))
    bare = [s for s in SPANS if s[0].startswith("bench.")]
    without = tr.reduce(ProfileData.from_serialized_xspace(
        xspace(spans=bare)))
    assert with_program == without


def test_operator_trace_without_a_bench_window(tmp_path):
    program = [s for s in SPANS if s[0].startswith("blade.")]
    path = tmp_path / "h.xplane.pb"
    path.write_bytes(xspace(spans=program))
    r = reduced(str(path))
    assert r["window_s"] == pytest.approx(880e-9)      # the blade.run span
    assert r["stages"]["local_train"] == pytest.approx(230e-9)
