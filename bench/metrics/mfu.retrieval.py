"""Whole step, throughput view: the model FLOPs of the window's replies
over the window, as a share of the chip's peak bf16 rate."""


def read(run):
    if run.replies_in_window == 0:
        return None
    rate = run.request_flops * run.replies_in_window / run.seconds
    return 100.0 * rate / run.peak["bf16_flops_per_s"]
