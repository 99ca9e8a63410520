"""Model families are found by the name a configuration states in
``model.arch``; the MLP family builds the inputs it built before it was a
family, bit for bit; and a family, its configuration and a cell added as
new files and entries only run to ``correct``."""
import hashlib
import json
import os

import jax
import numpy as np
import pytest

import families
import flops
import inputs
from checkout import KEYS, ROOT, checkout, cpu_run, limits_of, result
from families import mlp

BENCH = os.path.join(ROOT, "bench")


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", [c["name"] for c in bench()["configs"]])
def test_every_config_names_a_family_module(name):
    cfg = config(name)
    family = families.of(cfg)
    assert family.__file__ == os.path.join(BENCH, "families",
                                           cfg["model"]["arch"] + ".py")
    for fn in ("build", "to_host", "round_flops"):
        assert callable(getattr(family, fn))


UNKNOWN = {"model": {"arch": "no_such_family"}}


@pytest.mark.parametrize("entry", [families.of, flops.round_flops,
                                   lambda cfg: inputs.build(cfg, 0)],
                         ids=["of", "round_flops", "build"])
def test_unknown_arch_names_the_missing_file(entry):
    with pytest.raises(ModuleNotFoundError,
                       match=r"families/no_such_family\.py"):
        entry(UNKNOWN)


def digests(tree):
    """SHA-256 (first 16 hex digits) of each leaf's dtype, shape and bytes;
    keys by their key data."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if jax.dtypes.issubdtype(leaf.dtype, jax.dtypes.prng_key):
            leaf = jax.random.key_data(leaf)
        a = np.asarray(leaf)
        h = hashlib.sha256(f"{a.dtype}{a.shape}".encode() + a.tobytes())
        out[jax.tree_util.keystr(path)] = h.hexdigest()[:16]
    return out


# recorded on the CPU from the harness's inputs.build before the MLP code
# moved into families/mlp.py; both configs share the key chain, so the
# cohort config's leaves are the paper config's without the batch
PARAMS = {
    3: {"['key']": "644168f5628be9a3",
        "['params']['b1']": "a93e7175d10f7a9f",
        "['params']['b2']": "6f39c9482b42d162",
        "['params']['w1']": "6e2c627db80a2779",
        "['params']['w2']": "7973a21411749baf",
        "['run_key']": "3acbe2b3b410cb6f"},
    2 ** 31 + 77: {"['key']": "3f42ac1190bc37a7",
                   "['params']['b1']": "a93e7175d10f7a9f",
                   "['params']['b2']": "6f39c9482b42d162",
                   "['params']['w1']": "67bae5f6a00867a2",
                   "['params']['w2']": "674f72d099e3d0a5",
                   "['run_key']": "853c443b02f9570d"},
}
BATCH = {
    3: {"['batch']['x']": "3ce944b837226e21",
        "['batch']['y']": "a7783bc76f2288bc"},
    2 ** 31 + 77: {"['batch']['x']": "62f6258bfd37246c",
                   "['batch']['y']": "330bcbc93d48acbc"},
}
PINNED = [("blade_mlp_mnist_c20", s, {**PARAMS[s], **BATCH[s]})
          for s in PARAMS] + [("blade_mlp_cohort_10k", s, PARAMS[s])
                              for s in PARAMS]


@pytest.mark.parametrize("name,seed,want", PINNED)
def test_mlp_inputs_are_bit_identical_to_the_parents(name, seed, want):
    cfg = config(name)
    assert digests(mlp.build(cfg, seed)) == want
    assert digests(inputs.build(cfg, seed)) == want


def test_mlp_host_copy():
    got = mlp.build(config("blade_mlp_mnist_c20"), 11)
    host = mlp.to_host(got)
    assert sorted(host) == ["params", "x", "y"]
    for n, v in got["params"].items():
        assert host["params"][n].dtype == np.float64
        np.testing.assert_array_equal(host["params"][n], np.asarray(v))
    np.testing.assert_array_equal(np.asarray(host["x"]),
                                  np.asarray(got["batch"]["x"]))
    np.testing.assert_array_equal(np.asarray(host["y"]),
                                  np.asarray(got["batch"]["y"]))
    cohort = mlp.to_host(mlp.build(config("blade_mlp_cohort_10k"), 11))
    assert sorted(cohort) == ["params"]


ALIAS = "from families.mlp import build, round_flops, to_host  # noqa: F401\n"


def test_a_family_added_as_new_files_runs_a_cell(tmp_path):
    """A stub family that re-exports the MLP's, a configuration that names
    it and a cell that runs it, added to a copy of the checkout as new
    files and entries only: the cell runs to ``correct`` with the
    contract's result keys."""
    paper = next(c for c in bench()["configs"]
                 if c["name"] == "blade_mlp_mnist_c20")
    entry = dict(paper, name="blade_mlp_alias_c20",
                 file="bench/configs/blade_mlp_alias_c20.json")
    cfg = config("blade_mlp_mnist_c20")
    cfg["name"] = entry["name"]
    cfg["model"] = dict(cfg["model"], arch="mlp_alias")
    cell = {"name": "alias_c20_k5", "config": entry["name"],
            "traffic": "closed_k5", "chips": 1,
            "why": "the paper round through a family found by its name"}
    root = checkout(tmp_path, cells=[cell], configs=[(entry, cfg)],
                    limits={cell["name"]: limits_of("paper_c20_k5")},
                    files={"bench/families/mlp_alias.py": ALIAS})
    proc = cpu_run("--workload", cell["name"], "--seed", str(2 ** 31 + 41),
                   "--seconds", "1", "--trace", "0", root=root)
    out = result(proc)
    assert list(out) == KEYS
    assert out["correct"] is True
    assert set(out["metrics"]) == {"rounds_per_s", "run_p95_ms", "setup_s"}
    assert out["device"]["platform"] == "cpu"
