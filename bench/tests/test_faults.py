"""A run whose timed path is broken underneath comes out not correct:
once for each fault a cell can have. On the CPU, at the cell's shapes."""
import pytest

from test_rehearsal import ROOT, cpu_run, result, x4_checkout

FAULTS = [
    ("paper_c20_k5", 1, "state_unchanged"),
    ("paper_c20_k5", 1, "half_batch"),
    ("paper_c20_k5", 1, "altered_winner"),
    ("paper_c20_k5_x4", 4, "no_exchange"),
    ("cohort_10k_a64", 1, "state_unchanged"),
    ("cohort_10k_a64", 1, "altered_cohort"),
]


@pytest.mark.parametrize("cell,devices,fault", FAULTS)
def test_fault_is_not_correct(cell, devices, fault, tmp_path):
    root = x4_checkout(tmp_path) if devices > 1 else ROOT
    out = result(cpu_run("--fault", fault, "--workload", cell, "--seed", "8",
                         "--seconds", "1", "--trace", "0", devices=devices,
                         root=root))
    assert out["correct"] is False
    failing = [n for n, c in out["checks"].items()
               if not c["value"] <= c["limit"]]
    assert failing, out["checks"]
