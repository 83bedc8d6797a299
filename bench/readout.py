"""Arithmetic shared by the metric readers."""
from __future__ import annotations

import math


def p95(values) -> float | None:
    """95th percentile by nearest rank: the smallest value that at least 95%
    of the sample does not exceed."""
    vals = sorted(values)
    if not vals:
        return None
    return vals[math.ceil(0.95 * len(vals)) - 1]


def traced_steps(run):
    """The window's steps that the trace holds, paired with their device
    busy seconds; None where the run was not traced or the trace's step
    spans do not match the harness's steps one to one."""
    if run.trace is None:
        return None
    first, last = run.timeline.traced_steps
    steps = run.timeline.steps[first:last]
    if not steps or len(steps) != len(run.trace.step_busy_s):
        return None
    return list(zip(steps, run.trace.step_busy_s))


def least_times(run, pairs):
    """Per traced step: (FLOPs, bytes, least seconds) from bench/work/."""
    m, peak = run.cell.conf["model"], run.peak
    out = []
    for step, _ in pairs:
        f = run.work.step_flops(m, step.active, step.rows)
        b = run.work.step_bytes(m, step.active, step.rows)
        out.append((f, b, max(f / peak["bf16_flops_per_s"],
                              b / peak["hbm_bytes_per_s"])))
    return out
