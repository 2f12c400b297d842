"""Whole step, latency view: the model FLOPs of one request over that
run's median latency, as a share of the chip's peak bf16 rate."""


def read(run):
    p50 = run.pct(50)
    if p50 is None:
        return None
    return 100.0 * run.request_flops / (p50 / 1e3
                                        * run.peak["bf16_flops_per_s"])
