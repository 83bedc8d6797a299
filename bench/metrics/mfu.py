"""Whole step: model FLOPs of every token the traced steps processed
(prompt and output alike, bench/work/) over the traced window's length and
the chip's peak bf16 FLOP/s, in percent."""
from bench.readout import least_times, traced_steps


def read(run):
    pairs = traced_steps(run)
    if not pairs or run.trace.window_s <= 0:
        return None
    flops = sum(f for f, _, _ in least_times(run, pairs))
    return 100.0 * flops / (run.trace.window_s * run.peak["bf16_flops_per_s"])
