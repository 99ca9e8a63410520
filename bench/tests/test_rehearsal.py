"""Each cell's run, end to end on the CPU at the cell's own shapes, about
one second of window: the result line has exactly the contract's keys.

The 4-chip cell ``paper_c20_k5_x4`` (traffic ``closed_k5_sharded4``) is
not in ``BENCHMARK.json`` yet: it was not proven on chips. It runs here on
four host devices from a copy of the checkout that adds its entry."""
import os
import subprocess
import sys

import pytest

from checkout import KEYS, ROOT, checkout, cpu_run, limits_of, result

X4 = {"name": "paper_c20_k5_x4", "config": "blade_mlp_mnist_c20",
      "traffic": "closed_k5_sharded4", "chips": 4,
      "why": "the paper round sharded 5 clients per chip over 4 chips"}


def x4_checkout(tmp_path):
    """A copy of the checkout whose BENCHMARK.json has the 4-chip cell,
    held to the paper cell's limits."""
    return checkout(tmp_path, cells=[X4],
                    limits={X4["name"]: limits_of("paper_c20_k5")})


CELLS = [("paper_c20_k5", 1), ("cohort_10k_a64", 1), ("paper_c20_k5_x4", 4)]


@pytest.mark.parametrize("cell,devices", CELLS)
def test_untraced_run(cell, devices, tmp_path):
    root = x4_checkout(tmp_path) if devices > 1 else ROOT
    proc = cpu_run("--workload", cell, "--seed", str(2 ** 31 + 99),
                   "--seconds", "1", "--trace", "0", devices=devices,
                   root=root)
    out = result(proc)
    assert list(out) == KEYS
    assert out["correct"] is True
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] == devices
    assert {"rounds_per_s", "setup_s"} <= set(out["metrics"])
    assert ("run_p95_ms" in out["metrics"]) == cell.startswith("paper")
    # the numbers compared are the last lines of standard error
    tail = proc.stderr.strip().splitlines()[-len(out["checks"]):]
    assert [t.split(":")[0] for t in tail] == [f"check {n}"
                                               for n in out["checks"]]
    for line in proc.stdout.strip().splitlines()[:-1]:
        assert '"device": {"platform": "cpu"' in line


def test_traced_run():
    """On the CPU the trace holds no TPU plane: the device metrics are left
    out and the host-span metrics stay."""
    out = result(cpu_run("--workload", "cohort_10k_a64", "--seed", "5",
                         "--seconds", "1", "--trace", "1"))
    assert list(out) == KEYS
    assert out["correct"] is True
    assert set(out["metrics"]) == {"ledger_ms_per_round", "data_ms_per_round",
                                   "store_ms_per_round"}


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "bench/run.py", "--workload",
                           "paper_c20_k5", "--seed", "1", "--seconds", "1"],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 2 and proc.stdout == ""
