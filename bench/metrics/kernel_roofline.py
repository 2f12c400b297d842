"""Kernel layer: the least time the chip could take for the corpus
kernel's launches of the window (per launch the larger of its FLOPs at
peak bf16 rate and its bytes at peak HBM rate, ``bench/work.py``), over
the summed device time of the kernel's events in the trace, in percent."""

from bench import work


def read(run):
    t = run.trace
    if t is None or t["kernel_launches"] == 0 or t["kernel_s"] <= 0:
        return None
    least, _ = work.roofline_seconds(run.launch_flops, run.launch_bytes,
                                     run.peak)
    return 100.0 * t["kernel_launches"] * least / t["kernel_s"]
