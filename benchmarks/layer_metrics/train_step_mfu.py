"""The whole training step's share of the chip's bf16 peak: the window's
host-clock throughput in units of the peak. It is the samples a second a chip
that the generator's stopwatch gave for this run's window, times the
configuration's ``model_flops_per_sample`` (the forward and backward
multiply-adds its shapes need, nothing recomputed), over ``bf16_flops_per_s``
of the device's row of ``harness/peaks.json``: a constant times the rate, so
it moves with ``train_samples_per_s_per_chip`` and never apart from it. What
it adds is the unit: it bounds what the kernels' rooflines can claim, and a
kernel taken off the path leaves its roofline silent while this still reads.

Like every per-layer metric it is read in the traced run, whose one window is
the traced one (3 s under the profiler, two or three dispatches), so one
stall moves it more than the 20 s rate; the 20 s window's share is the line
``model FLOPs utilisation`` that the generator logs in every run, through the
same ``gate.share_of_peak``. A reading over 100 is a wrong FLOP count or a
wrong clock and is raised, not reported or clipped. Source: host clock."""

from benchmarks.harness.gate import share_of_peak


def read(run):
    rate = run.result["end_to_end"].get("train_samples_per_s_per_chip")
    if rate is None:    # a cell that trains nothing
        return None
    flops = run.cell.config_module().model_flops_per_sample(run.cell.sizes)
    share = share_of_peak(flops, rate, run.peaks)
    if share > 100.0:
        raise ValueError(
            f"train_step_mfu reads {share:.1f}% in {run.cell.name}: "
            f"{flops:.4g} FLOPs a sample at {rate:.6g} samples/s/chip is over "
            f"the chip's {run.peaks['bf16_flops_per_s']:.4g} FLOP/s, so "
            "model_flops_per_sample counts too much or the window's time "
            "leaves work out")
    return share
