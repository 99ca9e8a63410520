"""The harness finds every piece by name, and a configuration states the
RoundSpec that ``repro.launch.train`` derives from the same arguments."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import run
from drivers.common import round_spec
from repro.launch import train

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", [c["name"] for c in bench()["workloads"]])
def test_every_piece_of_a_cell_is_found(cell):
    b, entry, cfg, traffic, limits = run.find_cell(cell)
    assert cfg["name"] == entry["config"]
    assert os.path.exists(os.path.join(BENCH, "drivers",
                                       traffic["driver"] + ".py"))
    assert limits
    reported = run.metrics_of(b, entry, "end_to_end") + \
        run.metrics_of(b, entry, "per_layer")
    for m in reported:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
    assert {"setup_s", "rounds_per_s"} <= {m["name"] for m in reported}


def test_config_files_are_the_benchmark_entries():
    for c in bench()["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]


class Captured(Exception):
    pass


def _capture(monkeypatch, entry):
    def grab(loss_fn, spec, *args, **kwargs):
        raise Captured(spec)
    monkeypatch.setattr(train.rounds, entry, grab)


ARGS = ["--clients", "20", "--k", "5", "--t-sum", "100", "--alpha", "1",
        "--beta", "10"]


def test_paper_spec_is_what_the_cli_derives(monkeypatch):
    _capture(monkeypatch, "run_blade_fl")
    with open(os.path.join(BENCH, "configs", "blade_mlp_mnist_c20.json")) as f:
        cfg = json.load(f)
    with pytest.raises(Captured) as got:
        train.run(train.parse_args(ARGS + ["--lazy", "4", "--sigma2", "0.01"]))
    assert got.value.args[0] == round_spec(cfg)


def test_cohort_spec_is_what_the_cli_derives(monkeypatch):
    _capture(monkeypatch, "run_blade_fl_cohort")
    with open(os.path.join(BENCH, "configs",
                           "blade_mlp_cohort_10k.json")) as f:
        cfg = json.load(f)
    with pytest.raises(Captured) as got:
        train.run(train.parse_args(ARGS + ["--enrolled", "10000",
                                           "--cohort", "64"]))
    assert got.value.args[0] == round_spec(cfg)


NEW_METRIC = '''
def read(run):
    return float(run.window["calls"])
'''


def test_a_new_cell_and_metric_need_only_new_files(tmp_path):
    """A traffic file, a limits file and a metric file dropped into a copy
    of the benchmark, with their entries in BENCHMARK.json, are found with
    no edit of the harness."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    b = bench()
    b["workloads"].append({"name": "paper_c20_k5_b", "config":
                           "blade_mlp_mnist_c20", "traffic": "closed_k5_b",
                           "chips": 1, "why": "a new cell"})
    b["per_layer"].append({"name": "calls_made", "unit": "calls",
                           "better": "higher", "source": "host_clock",
                           "layer": "driver", "moves": "rounds_per_s",
                           "workloads": ["paper_c20_k5_b"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    shutil.copy(tmp_path / "bench/traffic/closed_k5.json",
                tmp_path / "bench/traffic/closed_k5_b.json")
    shutil.copy(tmp_path / "bench/limits/paper_c20_k5.json",
                tmp_path / "bench/limits/paper_c20_k5_b.json")
    (tmp_path / "bench/metrics/calls_made.py").write_text(NEW_METRIC)
    probe = ("import types, run\n"
             "b, cell, cfg, traffic, limits = run.find_cell('paper_c20_k5_b')\n"
             "names = [m['name'] for m in run.metrics_of(b, cell, 'per_layer')]\n"
             "value = run.read_metric('calls_made', types.SimpleNamespace("
             "window={'calls': 3}))\n"
             "print(cfg['name'], traffic['driver'], names, value)\n")
    out = subprocess.run([sys.executable, "-c", probe],
                         cwd=tmp_path / "bench", capture_output=True,
                         text=True, check=True).stdout
    assert out.split() == ["blade_mlp_mnist_c20", "scan_runs",
                           "['calls_made']", "3.0"]
