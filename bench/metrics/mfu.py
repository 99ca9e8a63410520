"""Model FLOP utilization of the traced window: training FLOPs per round
(``flops.round_flops``) times the rounds completed in the traced window,
over the window (from the device trace) times chips times each chip's
bf16 peak (percent). bf16 is the peak because the training matmuls run
one bf16 pass."""
import flops


def read(run):
    if not run.trace or not run.trace.get("n_devices") or not run.rounds_traced:
        return None
    work = flops.round_flops(run.cfg) * run.rounds_traced
    return 100.0 * work / (run.trace["window_s"] * run.chips
                           * flops.peak(run.device["kind"]))
