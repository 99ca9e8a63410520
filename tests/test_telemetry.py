"""The engine's names in a profiler trace (``core/telemetry.py``).

  * **host spans** — under ``jax.profiler.trace`` the scan, loop and cohort
    drivers emit exactly their documented ``blade.*`` spans, each inside
    its ``blade.run``, carrying their counts (rounds, clients, PoW hashes,
    rows gathered, datasets drawn).
  * **stage scopes** — the compiled scan runner names every op by stage
    in its ``op_name`` metadata.
  * **no effect on results** — params, history and ledger are bitwise the
    same with a profiler session active and without one.
  * **operator entry point** — ``launch/train --trace-dir`` writes a trace
    holding ``blade.run``.
"""
import dataclasses
import glob
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import rounds, telemetry, topology
from repro.data.pipeline import CohortDataSource, FLDataSource
from repro.launch import train
from repro.models.mlp import init_mlp, mlp_loss

C, SAMPLES, K = 8, 64, 2
ENROLLED = 40

SPEC = rounds.RoundSpec(n_clients=C, tau=2, eta=0.05, n_lazy=2, sigma2=0.01,
                        mine_attempts=256, difficulty_bits=2)
SCAN_SPANS = ["blade.plan", "blade.init", "blade.dispatch", "blade.fetch",
              "blade.history", "blade.ledger"]
COHORT_ROUND_SPANS = ["blade.cohort", "blade.data", "blade.store.gather",
                      "blade.dispatch", "blade.fetch", "blade.store.scatter",
                      "blade.fetch", "blade.ledger", "blade.history"]


@pytest.fixture(scope="module")
def paper():
    key = jax.random.key(0)
    batch = FLDataSource(key, C, SAMPLES, 0.5, seed=0).static_batch()
    return init_mlp(jax.random.fold_in(key, 1)), batch, jax.random.key(2)


def blade_spans(trace_dir):
    """[(start_ns, end_ns, name, {stat: value})] of the blade.* events of
    the trace under ``trace_dir``, in time order."""
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = [(ev.start_ns, ev.end_ns, ev.name, dict(ev.stats))
           for plane in ProfileData.from_file(path).planes
           for line in plane.lines for ev in line.events
           if ev.name.startswith(telemetry.PREFIX)]
    return sorted(out, key=lambda s: (s[0], -s[1]))


def children(spans, parent):
    """The spans directly below ``parent`` (nested in it, in no other)."""
    inside = [s for s in spans if s is not parent
              and parent[0] <= s[0] and s[1] <= parent[1]]
    return [s for s in inside if not any(
        o is not s and o[0] <= s[0] and s[1] <= o[1] for o in inside)]


def run_scan(paper, trace_dir=None):
    params, batch, key = paper
    if trace_dir is None:
        return rounds.run_blade_fl(mlp_loss, SPEC, params, batch, key, K)
    with jax.profiler.trace(str(trace_dir)):
        return rounds.run_blade_fl(mlp_loss, SPEC, params, batch, key, K)


def run_cohort(trace_dir=None):
    spec = dataclasses.replace(SPEC, n_lazy=0, sigma2=0.0)
    key = jax.random.key(5)
    source = CohortDataSource(key, SAMPLES, 0.5)
    cohort = topology.CohortSchedule.from_spec(ENROLLED, C, "uniform")
    args = (mlp_loss, spec, init_mlp(jax.random.fold_in(key, 1)),
            source.cohort_batch, jax.random.key(6), K, cohort)
    if trace_dir is None:
        return rounds.run_blade_fl_cohort(*args)
    with jax.profiler.trace(str(trace_dir)):
        return rounds.run_blade_fl_cohort(*args)


def test_scan_driver_spans_and_counts(paper, tmp_path):
    run_scan(paper)                          # compile outside the trace
    run_scan(paper, tmp_path)
    assert rounds.LAST_DISPATCH["driver"] == "scan"
    spans = blade_spans(tmp_path)
    (run,) = [s for s in spans if s[2] == "blade.run"]
    assert {s[2] for s in spans} == {"blade.run", "blade.ledger.validate",
                                     *SCAN_SPANS}
    assert all(run[0] <= s[0] and s[1] <= run[1] for s in spans)
    assert [s[2] for s in children(spans, run)] == SCAN_SPANS
    assert run[3] == {"rounds": K, "clients": C,
                      "hashes": K * C * SPEC.mine_attempts}
    named = {s[2]: s[3] for s in spans}
    assert named["blade.ledger"] == {"blocks": K}
    assert named["blade.ledger.validate"] == {"blocks": K}
    assert named["blade.fetch"]["bytes"] > 0


def test_loop_driver_uses_the_same_names(paper, tmp_path):
    params, _, key = paper
    micro = FLDataSource(key, 4, 8, 0.5, seed=0).static_batch()
    spec = dataclasses.replace(SPEC, n_clients=4, n_lazy=1)
    rounds.run_blade_fl(mlp_loss, spec, params, micro, key, K)
    with jax.profiler.trace(str(tmp_path)):
        rounds.run_blade_fl(mlp_loss, spec, params, micro, key, K)
    assert rounds.LAST_DISPATCH["driver"] == "loop"
    spans = blade_spans(tmp_path)
    (run,) = [s for s in spans if s[2] == "blade.run"]
    per_round = ["blade.dispatch", "blade.fetch", "blade.ledger",
                 "blade.history"]
    assert [s[2] for s in children(spans, run)] == (
        ["blade.plan", "blade.init"] + per_round * K)
    assert [s[3]["round"] for s in children(spans, run)
            if s[2] == "blade.dispatch"] == list(range(K))


def test_cohort_driver_spans_and_counts(tmp_path):
    run_cohort()
    store, hist, _ = run_cohort(tmp_path)
    spans = blade_spans(tmp_path)
    (run,) = [s for s in spans if s[2] == "blade.run"]
    assert all(run[0] <= s[0] and s[1] <= run[1] for s in spans)
    assert [s[2] for s in children(spans, run)] == (
        ["blade.plan"] + COHORT_ROUND_SPANS * K)
    assert run[3]["hashes"] == K * C * SPEC.mine_attempts
    named = lambda n: [s for s in spans if s[2] == n]   # noqa: E731
    assert [s[3]["round"] for s in named("blade.cohort")] == list(range(K))
    assert [s[3]["round"] for s in named("blade.data")] == list(range(K))
    assert all(s[3]["rows"] == C for s in named("blade.store.gather"))
    assert all(s[3]["bytes"] > 0 for s in named("blade.store.gather"))
    scatter = named("blade.store.scatter")
    assert all(s[3]["rows"] == C for s in scatter)
    assert sum(s[3]["new_rows"] for s in scatter) == store.touched
    # one fresh source per call: every dataset is drawn once, then hit
    fresh = {c for h in hist for c in h["cohort"]}
    data = named("blade.data")
    assert sum(s[3]["draws"] for s in data) == len(fresh)
    assert sum(s[3]["draws"] + s[3]["hits"] for s in data) == K * C
    assert len(named("blade.data.draw")) == len(fresh)
    assert all(any(d[0] <= s[0] and s[1] <= d[1] for d in data)
               for s in named("blade.data.draw"))


def test_scan_runner_names_every_stage(paper):
    params, batch, key = paper
    runner = rounds._scan_runner(mlp_loss, SPEC, K, False)
    state = rounds.init_state(params, key, C)
    text = runner.lower(state, batch).compile().as_text()
    parts = {p for name in re.findall(r'op_name="([^"]*)"', text)
             for p in name.split("/")}
    assert {"local_train", "perturb", "communicate", "mine",
            "finalize"} <= parts
    assert set(telemetry.STAGES) - parts == {"attack"}   # no attack set


def test_results_bitwise_with_and_without_a_profiler(paper, tmp_path):
    def flat(out):
        state, hist, ledger = out
        return (jax.tree.map(np.asarray, state.params), hist, ledger.blocks)

    plain = flat(run_scan(paper))
    traced = flat(run_scan(paper, tmp_path / "scan"))
    jax.tree.map(np.testing.assert_array_equal, plain[0], traced[0])
    assert plain[1:] == traced[1:]
    store, hist, ledger = run_cohort()
    t_store, t_hist, t_ledger = run_cohort(tmp_path / "cohort")
    assert (hist, ledger.blocks) == (t_hist, t_ledger.blocks)
    ids = np.asarray(sorted({c for h in hist for c in h["cohort"]}))
    jax.tree.map(np.testing.assert_array_equal,
                 jax.device_get(store.gather(ids)),
                 jax.device_get(t_store.gather(ids)))


def test_train_trace_dir_writes_blade_run(tmp_path):
    argv = ["--arch", "mlp", "--k", "1", "--clients", "4", "--beta", "0.5",
            "--t-sum", "3", "--trace-dir", str(tmp_path)]
    out = train.run(train.parse_args(argv))
    assert out.result["chain_valid"]
    names = {s[2] for s in blade_spans(tmp_path)}
    assert {"blade.run", "blade.dispatch"} <= names
