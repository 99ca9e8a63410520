"""Closed loop of K-round runs through ``rounds.run_blade_fl``.

Call ``i`` runs the cell's K rounds from the seed's initial model and
static batch with the run key ``fold_in(run_key, i)``, then validates the
ledger, as the CLI's result does. A seeded sample of the window's runs is
kept and checked against the reference once the window has closed.
"""
from __future__ import annotations

import contextlib

import jax
import numpy as np

import compare
import reference
from drivers.common import Reservoir, blocks_of, round_spec
from repro.core import chain, rounds
from repro.launch.mesh import make_client_mesh
from repro.models import mlp
from spans import patched


class Driver:
    def __init__(self, run):
        self.run = run
        self.cfg = run.cfg
        self.spec = round_spec(self.cfg)
        self.k = self.cfg["budget"]["K"]
        self.loss = mlp.mlp_loss
        n_dev = run.traffic.get("mesh_devices", 0)
        self.mesh = make_client_mesh(n_dev) if n_dev else None
        self.params = run.inputs["params"]
        self.batch = run.inputs["batch"]
        if self.mesh is not None:
            sharding = jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec("data"))
            self.batch = jax.device_put(self.batch, sharding)
        self.run_key = run.inputs["run_key"]
        self.sample = Reservoir(run.traffic["check_runs"], run.seed)
        self.failed = 0

    def _call(self, i):
        spans = self.run.spans
        key = jax.random.fold_in(self.run_key, i)
        with spans.span("engine"):
            state, hist, ledger = rounds.run_blade_fl(
                self.loss, self.spec, self.params, self.batch, key, self.k,
                mesh=self.mesh)
        with spans.span("ledger"):
            self.failed += not ledger.validate_chain()
        return state, hist, ledger

    def warm_up(self):
        for i in range(self.run.traffic["warmup_calls"]):
            self._call(0xFFFFFFFF - i)   # keys no window call uses

    def call(self, i):
        state, hist, ledger = self._call(i)
        self.sample.offer(lambda: (i, state.params, hist, blocks_of(ledger)))
        return self.k

    @contextlib.contextmanager
    def traced(self):
        spans = self.run.spans
        with patched(chain, "ledger_from_scan",
                     spans.wrap("ledger", chain.ledger_from_scan)):
            yield

    def collect(self):
        """Bring the sampled runs to the host and drop their device state."""
        self.kept = sorted(
            (i, {n: np.asarray(v, np.float64) for n, v in params.items()},
             hist, blocks)
            for i, params, hist, blocks in self.sample.items.values())
        self.sample.items.clear()
        self.batch = self.params = None

    def counters(self):
        return {"dispatch": dict(rounds.LAST_DISPATCH),
                "mesh_devices": self.mesh.devices.size if self.mesh else 0,
                "trace_counts": dict(rounds.TRACE_COUNTS),
                "checked_runs": [i for i, *_ in self.kept]}

    def _reference(self, i, dtype):
        host = self.run.host_inputs
        key = jax.random.fold_in(self.run_key, i)
        return reference.train_rounds(host["params"], host["x"], host["y"],
                                      key, self.k, self.cfg["round_spec"],
                                      dtype)

    def _judge(self, produced):
        rs = self.cfg["round_spec"]
        init = self.run.host_inputs["params"]
        gaps = {"param_gap": 0.0, "loss_gap": 0.0}
        race = ledger = 0
        for i, params, hist, blocks in produced:
            ref_params, ref_hist, _ = self._reference(i, "float32")
            gaps["param_gap"] = max(gaps["param_gap"], compare.param_gap(
                params, ref_params, init))
            gaps["loss_gap"] = max(gaps["loss_gap"],
                                   compare.loss_gap(hist, ref_hist))
            replay = compare.Chain(rs["n_clients"], rs["mine_attempts"])
            replay.replay(blocks)
            race += replay.race_mismatches
            ledger += replay.ledger_mismatches
        return {**gaps, "race_mismatches": race, "ledger_mismatches": ledger,
                "runs_failed": self.failed}

    def check(self):
        return self._judge(self.kept)

    def control(self):
        """The reference in bfloat16 in the program's place, judged by the
        same numbers."""
        rs = self.cfg["round_spec"]
        produced = []
        for i, *_ in self.kept:
            params, hist, digests = self._reference(i, "bfloat16")
            produced.append((i, params, hist, control_blocks(
                digests, rs["n_clients"], rs["mine_attempts"])))
        return self._judge(produced)


def control_blocks(digests, n_clients, attempts, start=0,
                   head=reference.GENESIS):
    """Ledger headers that a run publishing ``digests`` would build."""
    link, out = reference.GENESIS, []
    for r, dig in enumerate(digests):
        w, n, h, link = reference.race(link, dig, r, n_clients, attempts)
        out.append((start + r, head, dig, w, n, h))
        head = reference.header_hash(*out[-1])
    return out
