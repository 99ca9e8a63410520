"""Bring-up check of the BLADE-FL engine on a TPU: one chip, or one 4-chip host.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the sharded scan only

It drives ``repro.launch.train``'s run functions with their own arguments at
the paper's §7.1 width. That is the MLP 784-256-10 and N=20 clients with 512
samples each of the synthetic MNIST proxy made from ``--seed``. The budget is
t_sum=100 and beta=10, so tau=10 local steps and 10240 PoW attempts, and the
run is K=5 rounds. It prints one line per phase and exits non-zero at the
first failed check. The last line of a passing run is the device record:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

One chip:
  device          the default backend is a TPU; without one it fails
  mlp             jnp path: compile s, steady rounds/s, chain, eval, dispatch
  mlp_pow_kernel  --kernels: Pallas PoW race in the program, headers bitwise
  mlp_fused       --kernels --fused-mix: params match mlp at the tolerance
                  tier; the jnp and the kernel mix matmuls both run at f32
  cohort          --enrolled 10000 --cohort 64 --k 3 through run_cohort
  cpu_reference   the mlp phase on the host CPU; global_loss gap to the chip

Four chips (``--chips 4``): the paper-scale run on a 4-chip ('data',) mesh
in gather and psum mode, and --clusters 2 on the 2x2 ('pod', 'data') mesh,
each against the single-device run on device 0: held to the tolerance tier
over one round, with the K-round gap and bitwise equality reported.

Everything runs in this one process: a child process could not reach a
chip this process holds.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.join(ROOT, "tests"))
# the cpu_reference phase needs the host's CPU backend next to the chip's
_platforms = os.environ.get("JAX_PLATFORMS", "")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax
import jax.numpy as jnp
import numpy as np

from equivalence import assert_trees_close
from repro.core import aggregation, rounds
from repro.kernels.fedavg.kernel import mix_rows_flat
from repro.launch import train
from repro.launch.compile_cache import enable_compile_cache
from repro.models.mlp import mlp_loss

PAPER = ["--arch", "mlp", "--clients", "20", "--k", "5", "--t-sum", "100",
         "--alpha", "1", "--beta", "10"]
# the tolerance tier of the K-round equivalence suites (tests/equivalence.py
# callers: test_fast_allreduce, test_cohort, test_sparse_mix)
TIER = {"rtol": 1e-5, "atol": 1e-6}
# Largest per-round relative gap in global_loss allowed between the chip
# and XLA:CPU. The chip runs f32 matmuls at its default precision, one bf16
# pass (inputs rounded to 2^-9 relative); the loss, a mean over 20 x 512
# samples, averages that down: a TPU v5 lite measured 4.1e-5 at seed 0. A
# fault of the chip path (a lost client, a wrong mix, a skipped step) moves
# the loss by percents.
CPU_GAP_BOUND = 1e-3
# Both mix matmuls (the engine's jnp mix and the fused kernel) run at full
# f32 (aggregation.MIX_PRECISION): normwise error near 1e-7, where one bf16
# pass gives 5e-3.
F32_MIX_BOUND = 1e-5
STEADY_REPS = 3


class Check(Exception):
    """A failed phase check; ends the run with a non-zero exit."""


def check(ok, msg):
    if not ok:
        raise Check(msg)


class CompileMeter:
    """Adds up JAX's compile events: seconds in backend compiles (a
    persistent-cache load counts as one), their number, how many of them
    took under a second, and persistent-cache hits."""

    def __init__(self):
        self.secs = self.small_secs = 0.0
        self.count = self.small = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.count += 1
            if secs < 1.0:
                self.small += 1
                self.small_secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def since(self, mark):
        return {"compile_s": self.secs - mark[0],
                "compiles": self.count - mark[1],
                "cache_hits": self.hits - mark[2]}

    def mark(self):
        return (self.secs, self.count, self.hits)


def report(name, **fields):
    print(f"{name}: {json.dumps(fields, default=str)}", flush=True)


def headers(ledger):
    return [(b.model_digest, b.winner, b.nonce, b.pow_hash)
            for b in ledger.blocks]


def losses_ok(out):
    vals = [h["global_loss"] for h in out.history]
    vals += [h["local_loss_mean"] for h in out.history]
    vals.append(out.result["final_eval_loss"])
    return all(np.isfinite(v) for v in vals)


def trees_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def timed_phase(meter, argv):
    """One cold run (compiles), then STEADY_REPS warm runs of the same
    arguments. Returns (cold output, fields to report)."""
    args = train.parse_args(argv)
    mark = meter.mark()
    cold = train.run(args)
    fields = meter.since(mark)
    mark = meter.mark()
    walls = [train.run(args).result["wall_s"] for _ in range(STEADY_REPS)]
    fields["steady_wall_s"] = statistics.median(walls)
    fields["rounds_per_s"] = cold.result["K"] / fields["steady_wall_s"]
    fields["steady_compiles"] = meter.since(mark)["compiles"]
    fields["chain_valid"] = cold.result["chain_valid"]
    fields["final_eval_loss"] = cold.result["final_eval_loss"]
    fields["final_eval_acc"] = cold.result["final_eval_acc"]
    fields["dispatch"] = cold.result["dispatch"]
    return cold, fields


def check_run(name, out):
    check(out.result["chain_valid"], f"{name}: ledger chain does not validate")
    check(losses_ok(out), f"{name}: a loss is not finite")


def require_tpu():
    """The device phase: a TPU default backend, or the run ends here."""
    backend = jax.default_backend()
    if backend != "tpu":
        raise Check(f"device: default backend is {backend!r}, not a TPU; "
                    "this check runs on the chip only")
    dev = jax.devices()[0]
    report("device", platform=dev.platform, kind=dev.device_kind,
           count=len(jax.devices()))
    return dev


def matmul_precision(seed):
    """Which precision the f32 mix matmul runs at on this device: an
    unmarked ``jnp.dot`` (XLA's default), the engine's jnp mix
    (``aggregation.mix``) and the fused Pallas kernel, at the MLP's widest
    leaf (20x20 @ 20x200704). Errors are normwise, relative to a float64
    host product; one bf16 pass gives about 5e-3, full f32 about 1e-7."""
    kw, kx = jax.random.split(jax.random.key(seed))
    w = jax.nn.softmax(jax.random.normal(kw, (20, 20)), axis=1)
    x = jax.random.normal(kx, (20, 784 * 256))
    want = np.asarray(w, np.float64) @ np.asarray(x, np.float64)

    def err(y):
        return float(np.abs(np.asarray(y, np.float64) - want).max()
                     / np.abs(want).max())

    return {
        "xla_default": err(jax.jit(jnp.dot)(w, x)),
        "engine_jnp_mix": err(jax.jit(
            lambda a, b: aggregation.mix({"x": b}, a)["x"])(w, x)),
        "pallas_kernel": err(jax.jit(lambda a, b: mix_rows_flat(
            a, b, interpret=False))(w, x)),
    }


def one_chip(meter, seed):
    base = PAPER + ["--seed", str(seed)]

    mlp, fields = timed_phase(meter, base)
    check_run("mlp", mlp)
    hist = mlp.history
    check(hist[-1]["global_loss"] < hist[0]["global_loss"],
          f"mlp: global_loss did not fall ({hist[0]['global_loss']} -> "
          f"{hist[-1]['global_loss']})")
    report("mlp", **fields,
           global_loss=[h["global_loss"] for h in hist])

    pow_k, fields = timed_phase(meter, base + ["--kernels"])
    check_run("mlp_pow_kernel", pow_k)
    check(pow_k.result["dispatch"]["pow"] == "kernel",
          f"mlp_pow_kernel: dispatch {pow_k.result['dispatch']}")
    runner = rounds._scan_runner(mlp_loss, pow_k.spec, pow_k.result["K"],
                                 False)
    text = runner.lower(pow_k.state, pow_k.batch).compile().as_text()
    check("tpu_custom_call" in text,
          "mlp_pow_kernel: no tpu_custom_call in the compiled scan")
    same = headers(pow_k.ledger) == headers(mlp.ledger)
    check(same, "mlp_pow_kernel: block headers differ from the mlp phase")
    report("mlp_pow_kernel", **fields, tpu_custom_call=True,
           headers_bitwise_equal_mlp=same)

    fused, fields = timed_phase(meter, base + ["--kernels", "--fused-mix"])
    check_run("mlp_fused", fused)
    check(fused.result["dispatch"]["mix"] == "fused",
          f"mlp_fused: dispatch {fused.result['dispatch']}")
    try:
        assert_trees_close(fused.state.params, mlp.state.params, **TIER)
    except AssertionError as e:
        raise Check(f"mlp_fused: params outside the tolerance tier: {e}")
    errs = matmul_precision(seed)
    check(max(errs["engine_jnp_mix"], errs["pallas_kernel"]) <= F32_MIX_BOUND,
          f"mlp_fused: a mix matmul is not at f32 precision: {errs}")
    report("mlp_fused", **fields, params_within_tier=TIER,
           params_bitwise_equal_mlp=trees_equal(fused.state.params,
                                                mlp.state.params),
           mix_mode=fused.result["dispatch"]["mix_mode"],
           matmul_rel_err=errs)

    mark = meter.mark()
    cohort = train.run(train.parse_args(
        base + ["--enrolled", "10000", "--cohort", "64", "--k", "3"]))
    check(cohort.result["chain_valid"], "cohort: chain does not validate")
    report("cohort", **meter.since(mark), wall_s=cohort.result["wall_s"],
           chain_valid=True, touched=cohort.result["touched"],
           final_eval_loss=cohort.result["final_eval_loss"],
           dispatch=cohort.result["dispatch"])

    mark = meter.mark()
    with jax.default_device(jax.devices("cpu")[0]):
        cpu = train.run(train.parse_args(base))
    check_run("cpu_reference", cpu)
    gaps = [abs(a["global_loss"] - b["global_loss"]) / abs(b["global_loss"])
            for a, b in zip(mlp.history, cpu.history)]
    check(max(gaps) <= CPU_GAP_BOUND,
          f"cpu_reference: global_loss gap {max(gaps)} > {CPU_GAP_BOUND}")
    report("cpu_reference", **meter.since(mark), max_rel_gap=max(gaps),
           bound=CPU_GAP_BOUND, per_round_rel_gap=gaps,
           headers_bitwise_equal_chip=headers(cpu.ledger) == headers(
               mlp.ledger))


def placement(leaf):
    """Device id -> client rows [start, stop) of one carry leaf."""
    return {s.device.id: [s.index[0].start or 0,
                          s.index[0].stop or leaf.shape[0]]
            for s in leaf.addressable_shards}


def max_abs_diff(a, b):
    return max(float(np.abs(np.asarray(x) - np.asarray(y)).max())
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def tier_failure(many, one):
    """Why the sharded run's params and losses leave the tolerance tier of
    the single-device run; None when they hold it."""
    try:
        assert_trees_close(many.state.params, one.state.params, **TIER)
        assert_trees_close([h["global_loss"] for h in many.history],
                           [h["global_loss"] for h in one.history], **TIER)
    except AssertionError as e:
        return str(e)
    return None


def sharded_and_single(name, base, sharded_flags, single_flags):
    one = train.run(train.parse_args(base + single_flags))
    many = train.run(train.parse_args(base + sharded_flags))
    check_run(f"{name}_single", one)
    check_run(name, many)
    return many, one


def four_chips(seed):
    """Each sharded layout against the single-device run on device 0.

    The tier is held over one round, where the layout's mix is the only
    difference. Over K rounds it is reported, not gated: a mix that
    reassociates the fp32 sum (psum, and the cluster sums on the chip)
    leaves a last-ulp difference in the model, which flips a few ReLU
    gates in the next τ=10 local steps, and at this width the params then
    drift past the tier on any backend (XLA:CPU too)."""
    check(len(jax.devices()) >= 4,
          f"--chips 4 needs four devices, found {len(jax.devices())}")
    base = PAPER + ["--seed", str(seed)]
    cases = [("gather", ["--devices", "4"], []),
             ("psum", ["--devices", "4", "--fast-allreduce"], []),
             ("cluster", ["--clusters", "2", "--topology", "cluster:2"],
              ["--topology", "cluster:2"])]
    for name, sharded_flags, single_flags in cases:
        failure = tier_failure(*sharded_and_single(
            name, base + ["--k", "1"], sharded_flags, single_flags))
        check(failure is None,
              f"{name}: outside the tolerance tier over one round: {failure}")
        many, one = sharded_and_single(name, base, sharded_flags,
                                       single_flags)
        leaf = jax.tree.leaves(many.state.params)[0]
        check(len(leaf.sharding.device_set) == 4,
              f"{name}: carry spans {len(leaf.sharding.device_set)} devices")
        report(name, mesh_devices=str(leaf.sharding.mesh.devices.tolist()),
               mesh_axes=leaf.sharding.mesh.axis_names,
               carry_rows_by_device=placement(leaf),
               single_device=str(jax.tree.leaves(one.state.params)[0]
                                 .devices()),
               chain_valid=True, within_tier_one_round=TIER,
               within_tier_all_rounds=tier_failure(many, one) is None,
               params_max_abs_diff=max_abs_diff(many.state.params,
                                                one.state.params),
               params_bitwise=trees_equal(many.state.params,
                                          one.state.params),
               headers_bitwise=headers(many.ledger) == headers(one.ledger),
               cold_wall_s=many.result["wall_s"],
               single_cold_wall_s=one.result["wall_s"],
               dispatch=many.result["dispatch"])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded-scan comparison on a "
                         "4-chip host")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    try:
        dev = require_tpu()
        if args.chips == 4:
            four_chips(args.seed)
        else:
            one_chip(meter, args.seed)
        report("compile", cache_dir=cache_dir, total_s=meter.secs,
               compiles=meter.count, under_1s=meter.small,
               under_1s_total_s=meter.small_secs, cache_hits=meter.hits)
    except Check as e:
        print(f"FAILED {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
