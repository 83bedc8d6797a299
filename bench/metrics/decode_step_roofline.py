"""Kernels: share of its roofline that the decode step reaches. For the
traced steps, the least time their work needs (per step the larger of FLOPs
over peak FLOP/s and bytes over HBM bandwidth, with FLOPs and bytes from
bench/work/) over the device's busy time during those steps."""
import sys

from bench.readout import least_times, traced_steps


def read(run):
    pairs = traced_steps(run)
    if not pairs:
        return None
    work = least_times(run, pairs)
    busy = sum(b for _, b in pairs)
    if busy <= 0:
        return None
    mem = sum(1 for f, b, t in work
              if b / run.peak["hbm_bytes_per_s"] >= t)
    print(f"bench: decode_step_roofline: {mem} of {len(work)} traced steps "
          f"are bound by HBM bandwidth, the rest by bf16 FLOP/s",
          file=sys.stderr)
    return 100.0 * sum(t for _, _, t in work) / busy
