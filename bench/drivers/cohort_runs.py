"""Closed loop of cohort runs through ``rounds.run_blade_fl_cohort``.

A long-running cross-device deployment: one population store, one data
source and one ledger live across calls; call ``i`` runs K rounds with the
run key ``fold_in(run_key, i)``, each round drawing a cohort, building its
data, gathering its rows, running one round and scattering the rows back.
Call 0 is a one-round warm-up that compiles every shape the window uses;
it belongs to the deployment, so the reference replays it too.
"""
from __future__ import annotations

import contextlib

import jax
import numpy as np

import compare
import reference
from drivers.common import blocks_of, round_spec
from drivers.scan_runs import control_blocks
from families.mlp import model_dims
from repro.core import chain, rounds, topology
from repro.data.pipeline import CohortDataSource
from repro.models import mlp
from spans import patched


class TracedStore(rounds.PopulationStore):
    """The program's store with a host span around its gather and its
    scatter. The scatter first waits for the round's output, so device
    time is not booked to the store: its ``device_get`` would wait there
    anyway."""

    spans = None

    def gather(self, idx):
        if not self.spans.on:
            return super().gather(idx)
        with self.spans.span("store"):
            return super().gather(idx)

    def scatter(self, idx, cohort_params):
        if not self.spans.on:
            return super().scatter(idx, cohort_params)
        jax.block_until_ready(cohort_params)
        with self.spans.span("store"):
            super().scatter(idx, cohort_params)


class Driver:
    def __init__(self, run):
        self.run = run
        cfg = self.cfg = run.cfg
        pop, data = cfg["population"], cfg["data"]
        self.spec = round_spec(cfg)
        self.k = cfg["budget"]["K"]
        self.loss = mlp.mlp_loss
        self.cohort = topology.CohortSchedule.from_spec(
            pop["enrolled"], pop["cohort"], pop["bias"])
        self.source = CohortDataSource(run.inputs["key"],
                                       data["samples_per_client"],
                                       data["dirichlet_alpha"])
        self.params = run.inputs["params"]
        self.store = TracedStore(self.params, pop["enrolled"])
        self.store.spans = run.spans
        self.ledger = chain.Ledger()
        self.run_key = run.inputs["run_key"]
        self.calls = []      # (call index, rounds, history)
        self.failed = 0

    def _call(self, i, k):
        spans = self.run.spans
        batches = (spans.wrap("data", self.source.cohort_batch) if spans.on
                   else self.source.cohort_batch)
        start = len(self.ledger.blocks)
        with spans.span("engine"):
            _, hist, _ = rounds.run_blade_fl_cohort(
                self.loss, self.spec, self.params, batches,
                jax.random.fold_in(self.run_key, i), k, self.cohort,
                ledger=self.ledger, store=self.store)
        with spans.span("ledger"):
            self.failed += not self.ledger.validate_chain()
        self.calls.append((i, k, hist, blocks_of(self.ledger, start)))
        return k

    def warm_up(self):
        self._call(0, self.run.traffic["warmup_rounds"])

    def call(self, i):
        return self._call(i + 1, self.k)

    @contextlib.contextmanager
    def traced(self):
        spans = self.run.spans
        with patched(chain, "make_block",
                     spans.wrap("ledger", chain.make_block)), \
                patched(chain.Ledger, "append",
                        spans.wrap("ledger", chain.Ledger.append)):
            yield

    def collect(self):
        """Read the touched rows back from the store, in cohort-sized
        blocks, and drop the program's device state."""
        ids = sorted({c for _, _, hist, _ in self.calls for h in hist
                      for c in h["cohort"]})
        a = self.cohort.cohort_size
        rows = {}
        for s in range(0, len(ids), a):
            block = ids[s:s + a]
            got = jax.device_get(self.store.gather(np.asarray(block)))
            for j, c in enumerate(block):
                rows[c] = {n: np.asarray(v[j], np.float64)
                           for n, v in got.items()}
        self.rows = rows
        self.params = None

    def counters(self):
        return {"dispatch": dict(rounds.LAST_DISPATCH),
                "touched": self.store.touched,
                "store_mb": self.store.materialized_bytes() / 1e6,
                "rounds_replayed": sum(k for _, k, _, _ in self.calls)}

    def _replay(self, dtype):
        """The reference over every call of the run: (per-call histories
        with cohorts, per-call digests, final rows of touched clients)."""
        cfg, rs = self.cfg, self.cfg["round_spec"]
        in_dim, _, n_classes = model_dims(cfg)
        data = reference.ClientData(self.run.inputs["key"],
                                    cfg["data"]["samples_per_client"],
                                    cfg["data"]["dirichlet_alpha"],
                                    in_dim, n_classes)
        init = self.run.host_inputs["params"]
        store, hists, digests = {}, [], []
        for i, k, _, _ in self.calls:
            key = jax.random.fold_in(self.run_key, i)
            hist, digs = [], []
            for _ in range(k):
                nxt, _, k_dp = reference.split3(key)
                ids = reference.cohort_members(k_dp, self.cohort.n_enrolled,
                                               self.cohort.cohort_size)
                rows = {n: np.stack([store.get(int(c), init)[n] for c in ids])
                        for n in init}
                x, y = data.batch(ids)
                mixed, local, glob, dig = reference.cohort_round(
                    rows, x, y, key, rs, dtype)
                mixed = {n: np.asarray(v, np.float64) for n, v in mixed.items()}
                for j, c in enumerate(ids):
                    store[int(c)] = {n: v[j] for n, v in mixed.items()}
                hist.append({"cohort": [int(c) for c in ids],
                             "local_loss_mean": local, "global_loss": glob})
                digs.append(dig)
                key = nxt
            hists.append(hist)
            digests.append(digs)
        return hists, digests, store

    def _judge(self, hists, blocks, rows, ref):
        rs = self.cfg["round_spec"]
        ref_hists, _, ref_rows = ref
        cohort = loss = 0.0
        for hist, ref_hist in zip(hists, ref_hists):
            cohort += sum(h["cohort"] != r["cohort"]
                          for h, r in zip(hist, ref_hist))
            loss = max(loss, compare.loss_gap(hist, ref_hist))
        replay = compare.Chain(rs["n_clients"], rs["mine_attempts"])
        for call_blocks in blocks:
            replay.replay(call_blocks)
        ids = sorted(ref_rows)
        init = self.run.host_inputs["params"]
        stack = {n: np.stack([rows.get(c, init)[n] for c in ids])
                 for n in init}
        ref_stack = {n: np.stack([ref_rows[c][n] for c in ids]) for n in init}
        return {"param_gap": compare.param_gap(stack, ref_stack, init),
                "loss_gap": loss, "cohort_mismatches": int(cohort),
                "race_mismatches": replay.race_mismatches,
                "ledger_mismatches": replay.ledger_mismatches,
                "runs_failed": self.failed}

    def check(self):
        return self._judge([h for _, _, h, _ in self.calls],
                           [b for _, _, _, b in self.calls], self.rows,
                           self._replay("float32"))

    def control(self):
        rs = self.cfg["round_spec"]
        hists, digests, rows = self._replay("bfloat16")
        blocks, start = [], 0
        head = reference.GENESIS
        for digs in digests:
            blocks.append(control_blocks(digs, rs["n_clients"],
                                         rs["mine_attempts"], start, head))
            start += len(digs)
            head = reference.header_hash(*blocks[-1][-1])
        return self._judge(hists, blocks, rows, self._replay("float32"))
