"""A copy of the checkout with cells added, and runs of ``cpu_run.py`` in
a checkout: what a rehearsal on the CPU needs to try a cell, a
configuration or a family that ``BENCHMARK.json`` does not have yet,
from new files and entries only."""
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
# the keys of a result line, in order
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def limits_of(cell):
    """The limits file of a cell of this checkout."""
    with open(os.path.join(ROOT, "bench", "limits", cell + ".json")) as f:
        return json.load(f)


def checkout(dest, cells=(), configs=(), limits=None, files=None):
    """Copy the checkout to ``dest`` (``bench/`` copied, ``src/`` linked)
    and add to it, as new files and entries only:

    - ``cells``: entries of ``BENCHMARK.json``'s ``workloads``; each is
      also added to the ``workloads`` list of every end-to-end metric
      that has one;
    - ``configs``: ``(entry, cfg)`` pairs, the entry added to ``configs``
      and ``cfg`` written as JSON to the entry's ``file``;
    - ``limits``: cell name -> its limits, written to
      ``bench/limits/<cell>.json``;
    - ``files``: path under ``dest`` -> its text.

    Returns ``dest``."""
    dest = str(dest)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), os.path.join(dest, "src"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for entry, cfg in configs:
        bench["configs"].append(entry)
        _write(dest, entry["file"], json.dumps(cfg, indent=1))
    for cell in cells:
        bench["workloads"].append(cell)
        for m in bench["end_to_end"]:
            if "workloads" in m:
                m["workloads"].append(cell["name"])
    _write(dest, "BENCHMARK.json", json.dumps(bench, indent=1))
    for name, lim in (limits or {}).items():
        _write(dest, f"bench/limits/{name}.json", json.dumps(lim))
    for path, text in (files or {}).items():
        _write(dest, path, text)
    return dest


def _write(dest, path, text):
    path = os.path.join(dest, path)
    if os.path.exists(path):
        raise FileExistsError(f"{path}: a checkout gains new files only")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


def cpu_run(*args, devices=1, timeout=900, root=ROOT):
    """``bench/tests/cpu_run.py`` with ``args`` in the checkout ``root``
    on ``devices`` host devices; fails the test on a nonzero exit."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if devices > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count={devices}")
    proc = subprocess.run([sys.executable,
                           os.path.join(root, "bench", "tests", "cpu_run.py"),
                           *args], cwd=root, env=env, capture_output=True,
                          text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc


def result(proc):
    """The result line of a run: the last line of its standard output."""
    return json.loads(proc.stdout.strip().splitlines()[-1])
