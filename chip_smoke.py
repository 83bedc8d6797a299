#!/usr/bin/env python3
"""Smoke test of the serving path and the Himeno kernel on a TPU chip.

    python chip_smoke.py               # one chip: serve, parity, Himeno
    python chip_smoke.py --four-chips  # only the sharded decode step, 4 chips

Everything runs in this one process: a chip belongs to one process at a
time. Phases, in order:

* device: fail at once unless JAX's first device is a TPU;
* serve: ``repro.launch.serve.serve`` at the published llama3.2-3b widths,
  8 requests of 16 new tokens through 4 slots;
* parity: the engine's tokens against the argmax of the teacher-forced
  ``forward`` over request 0, and a batch-1 ``decode_step`` replay against
  the same ``forward`` logits;
* himeno: the Pallas Jacobi kernel against the jnp reference at the
  paper's "L" grid.

``--four-chips`` runs only the decode step of ``launch/steps.py`` sharded
over a (data 1, model 4) mesh, against the one-chip ``decode_step`` on the
same parameters and tokens.

Any failed check exits non-zero. Times are informational one-off readings,
not a benchmark. The last line of stdout is one JSON object naming the
device.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "llama3.2-3b"
NUM_REQUESTS, SLOTS, MAX_NEW_TOKENS, MAX_LEN = 8, 4, 16, 64
# Decode (cached one-token attention) and forward (chunked attention) run
# the same bf16 model through different reductions. Their logits may differ
# by LOGIT_RTOL of the largest |logit| at a position; where two logits lie
# closer than twice that, their order is noise and the argmax is not held.
LOGIT_RTOL = 5e-2
HIMENO_GRID, HIMENO_ITERS = "L", 8
HIMENO_ATOL, GOSA_RTOL = 1e-5, 1e-4  # f32: one Jacobi update, ~1 ulp apart
FOUR_CHIPS, FOUR_CHIP_STEPS = 4, 4


def info(msg: str) -> None:
    print(msg, flush=True)


def check(ok, what) -> None:
    """Fail the run (also under ``python -O``, which drops asserts)."""
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


class CompileClock:
    """Sums JAX's backend-compile time (a persistent-cache hit counts its
    read time) and counts cache hits, from JAX's own monitoring events."""

    def __init__(self) -> None:
        import jax
        self.seconds, self.programs, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __str__(self) -> str:
        return (f"{self.seconds:.2f} s over {self.programs} programs, "
                f"{self.cache_hits} persistent-cache hits")


def check_device():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found platform "
                         f"{dev.platform!r}")
    info(f"device: {dev.device_kind}, {len(jax.devices())} device(s)")
    return dev


def relative_difference(ref, other) -> float:
    """max |other - ref| / max |ref|, over all positions and the vocab."""
    import numpy as np
    return float(np.max(np.abs(other - ref)) / np.max(np.abs(ref)))


def held_positions(ref):
    """Positions whose top-2 logit margin exceeds twice the tolerance, so
    that logits within LOGIT_RTOL of ``ref`` keep its argmax there."""
    import numpy as np
    top2 = np.sort(ref, axis=-1)[:, -2:]
    return (top2[:, 1] - top2[:, 0]
            > 2 * LOGIT_RTOL * np.max(np.abs(ref), axis=-1))


def serve_phase(arch: str, *, use_reduced: bool):
    from repro.configs import get_config, reduced
    from repro.launch.serve import serve

    cfg = get_config(arch)
    if use_reduced:
        cfg = reduced(cfg)
    t0 = time.perf_counter()
    out = serve(arch, use_reduced=use_reduced, num_requests=NUM_REQUESTS,
                slots=SLOTS, max_new_tokens=MAX_NEW_TOKENS, max_len=MAX_LEN)
    total = time.perf_counter() - t0
    info(f"serve (informational): set-up {total - out['wall_s']:.2f} s, "
         f"serving incl. decode-step compile {out['wall_s']:.2f} s, "
         f"{out['steps']} steps")
    check(out["completed"] == NUM_REQUESTS and out["rejected"] == 0, out)
    outputs = out["outputs"]
    check(sorted(outputs) == list(range(NUM_REQUESTS)), sorted(outputs))
    lengths = [len(o) for o in outputs.values()]
    check(lengths == [MAX_NEW_TOKENS] * NUM_REQUESTS, lengths)
    # the step that consumes the last prompt token emits the first output
    # token but counts as prefill
    check(out["decode_tokens"] == NUM_REQUESTS * (MAX_NEW_TOKENS - 1), out)
    tokens = [t for o in outputs.values() for t in o]
    check(all(0 <= t < cfg.vocab_size for t in tokens), "token out of range")
    info(f"serve: {out['completed']}/{NUM_REQUESTS} requests, "
         f"{len(tokens)} tokens, {out['decode_tokens']} decode tokens")
    return cfg, outputs[0]


def parity_phase(cfg, output: list[int]) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import models as M
    from repro.launch.serve import _requests

    prompt = _requests(1, MAX_NEW_TOKENS)[0].prompt
    seq = prompt + output[:-1]  # position p predicts token p + 1
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    fwd = jax.jit(lambda p, t: M.forward(cfg, p, {"tokens": t})[0])
    ref = np.asarray(fwd(params, jnp.asarray([seq], jnp.int32))[0],
                     np.float32)

    step = jax.jit(functools.partial(M.decode_step, cfg), donate_argnums=(1,))
    state = M.init_decode_state(cfg, 1, MAX_LEN)
    rows = []
    for tok in seq:
        logits, state = step(params, state, jnp.asarray([tok], jnp.int32))
        rows.append(np.asarray(logits[0], np.float32))
    rel = relative_difference(ref, np.stack(rows))

    emitted = ref[len(prompt) - 1:]
    held = held_positions(emitted)
    agree = np.argmax(emitted, axis=-1) == np.asarray(output)
    info(f"parity: decode vs forward max relative logit difference {rel:.4g} "
         f"(tolerance {LOGIT_RTOL}); engine tokens equal the forward argmax "
         f"at {int(np.sum(agree & held))}/{int(np.sum(held))} positions "
         f"whose top-2 margin exceeds {2 * LOGIT_RTOL} x max|logit| "
         f"({int(np.sum(agree))}/{len(output)} overall)")
    check(rel <= LOGIT_RTOL, rel)
    check(np.all(agree[held]), np.nonzero(held & ~agree))


def himeno_phase(grid, iters: int, impl: str) -> None:
    import jax
    import numpy as np
    from repro.kernels.himeno.ops import himeno_run
    from repro.kernels.himeno.ref import himeno_init

    state = himeno_init(grid)
    t0 = time.perf_counter()
    p_k, g_k = jax.block_until_ready(himeno_run(state, iters, impl=impl))
    t1 = time.perf_counter()
    p_r, g_r = jax.block_until_ready(himeno_run(state, iters, impl="ref"))
    t2 = time.perf_counter()
    err = float(np.max(np.abs(np.asarray(p_k) - np.asarray(p_r))))
    g_k, g_r = float(g_k), float(g_r)
    info(f"himeno {grid} x{iters} (informational, compile included): "
         f"{impl} {t1 - t0:.2f} s, ref {t2 - t1:.2f} s")
    info(f"himeno: max |p_{impl} - p_ref| {err:.3g} (tolerance {HIMENO_ATOL}),"
         f" gosa {g_k:.6g} vs {g_r:.6g} (rtol {GOSA_RTOL})")
    check(np.isfinite(g_k) and err <= HIMENO_ATOL, err)
    check(abs(g_k - g_r) <= GOSA_RTOL * abs(g_r), (g_k, g_r))


def four_chip_phase(arch: str, *, use_reduced: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import models as M
    from repro.configs import get_config, reduced
    from repro.configs.base import ShapeSpec
    from repro.launch.mesh import make_mesh_from_shape
    from repro.launch.steps import build_serve_step
    from repro.parallel.layouts import rules_for
    from repro.parallel.sharding import use_mesh

    check(len(jax.devices()) == FOUR_CHIPS, jax.devices())
    cfg = get_config(arch)
    if use_reduced:
        cfg = reduced(cfg)
    shape = ShapeSpec("smoke", "decode", MAX_LEN, SLOTS)
    mesh = make_mesh_from_shape({"data": 1, "model": FOUR_CHIPS})
    rules = rules_for(cfg, shape, mesh)
    prog = build_serve_step(cfg, shape, mesh, rules)
    p_shard, s_shard, t_shard = prog.in_shardings

    params = M.init_params(cfg, jax.random.PRNGKey(0))  # on device 0
    one_chip = jax.jit(functools.partial(M.decode_step, cfg),
                       donate_argnums=(1,))
    state = M.init_decode_state(cfg, SLOTS, MAX_LEN)
    sharded_params = jax.device_put(params, p_shard)
    # a state of its own: device_put may alias device 0's buffers, and both
    # steps donate their state
    sharded_state = jax.device_put(M.init_decode_state(cfg, SLOTS, MAX_LEN),
                                   s_shard)
    sharded = prog.jitted()
    tokens = np.arange(1, SLOTS + 1, dtype=np.int32)
    rels, held_agree = [], []
    for _ in range(FOUR_CHIP_STEPS):
        ref, state = one_chip(params, state, jnp.asarray(tokens))
        with use_mesh(mesh, rules):
            got, sharded_state = sharded(
                sharded_params, sharded_state,
                jax.device_put(jnp.asarray(tokens), t_shard))
        ref, got = np.asarray(ref, np.float32), np.asarray(got, np.float32)
        agree = np.argmax(ref, -1) == np.argmax(got, -1)
        rels.append(relative_difference(ref, got))
        held_agree.append(bool(np.all(agree[held_positions(ref)])))
        tokens = np.argmax(ref, -1).astype(np.int32)
    in_use = [d.memory_stats().get("bytes_in_use", 0) / 2**30
              if d.memory_stats() else float("nan") for d in jax.devices()]
    info(f"four chips: mesh {dict(mesh.shape)}, {FOUR_CHIP_STEPS} decode "
         f"steps, max relative logit difference vs one chip "
         f"{max(rels):.4g} (tolerance {LOGIT_RTOL}), argmax held "
         f"{held_agree}")
    info("four chips: GiB in use per device (device 0 also holds the "
         "one-chip copy): " + ", ".join(f"{g:.2f}" for g in in_use))
    check(max(rels) <= LOGIT_RTOL, rels)
    check(all(held_agree), held_agree)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the decode step sharded over 4 chips")
    args = ap.parse_args()

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    dev = check_device()
    info(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    if args.four_chips:
        four_chip_phase(ARCH, use_reduced=False)
    else:
        cfg, output = serve_phase(ARCH, use_reduced=False)
        gc.collect()  # drop the engine's parameters before re-initialising
        parity_phase(cfg, output)
        gc.collect()
        from repro.configs.himeno import GRIDS
        himeno_phase(GRIDS[HIMENO_GRID], HIMENO_ITERS, impl="pallas")
    info(f"compile (informational): {clock}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
