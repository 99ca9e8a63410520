"""The control, the plain reference in bfloat16 put in the program's
place, comes out not correct by the cell's limits, while the program
passes them; at the cell's own size on the CPU, for the paper cell."""
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)

PROBE = """
import json, sys, jax
sys.path.insert(0, {bench!r})
import run
run.require_devices = lambda chips: jax.devices()[:chips]
import compare
_, cell, cfg, traffic, limits = run.find_cell("paper_c20_k5")
run.use_checkout()
meter = run.CompileMeter()
r = run.new_run(cell, cfg, traffic, 12, jax.devices()[:1])
driver = run.set_up(r, meter, 0.0)
run.measure(r, driver, meter, 0.5, 0)
print(json.dumps([compare.judged(driver.check(), limits)[1],
                  compare.judged(driver.control(), limits)[1]]))
"""


def test_control_fails_and_program_passes():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", PROBE.format(bench=BENCH)],
                         env=env, capture_output=True, text=True, timeout=900,
                         check=True).stdout
    program_ok, control_ok = json.loads(out.strip().splitlines()[-1])
    assert program_ok and not control_ok
