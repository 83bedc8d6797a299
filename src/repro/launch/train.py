"""End-to-end training driver.

Production shape: config-driven, data pipeline + prefetch, jitted train step
built by launch.steps, async checkpointing with restart-resume, heartbeat /
straggler bookkeeping, optional GA offload search before the run (the
paper's Step 1–3 ahead of Step 6 deployment).

CPU-runnable: ``--arch llama3.2-3b --reduced --steps 200`` trains a toy-sized
model; the same path drives full configs on a real slice.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.checkpoint import Checkpointer
from repro.configs import SHAPES, get_config, reduced as reduce_cfg
from repro.configs.base import ShapeSpec
from repro.core import Decisions, GAConfig, search_lm_cell
from repro.data import DataConfig, SyntheticLMStream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import build_train_step, init_train_state
from repro.parallel.layouts import rules_for
from repro.parallel.sharding import use_mesh
from repro.runtime import StragglerDetector


def train(
    arch: str = "llama3.2-3b",
    *,
    use_reduced: bool = True,
    steps: int = 100,
    global_batch: int = 8,
    seq_len: int = 64,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 50,
    resume: bool = True,
    search_first: bool = False,
    log_every: int = 10,
    mesh=None,
) -> dict:
    cfg = get_config(arch)
    if use_reduced:
        cfg = reduce_cfg(cfg)
    shape = ShapeSpec("train_cli", "train", seq_len, global_batch)

    dec = None
    if search_first:
        mesh_shape = {"data": 16, "model": 16}
        res = search_lm_cell(cfg, SHAPES["train_4k"], mesh_shape,
                             GAConfig(population=8, generations=8))
        dec = res.best_decisions
        print(f"[search] best decisions: {dec}")

    rules = None
    if mesh is not None:
        rules = rules_for(cfg, shape, mesh)

    prog_mesh = mesh
    if mesh is None:
        # single-device CPU run: build the step without shardings
        import repro.models.transformer as T
        from repro.optim import AdamWConfig, adamw_update, init_opt_state

        opt_cfg = AdamWConfig(lr=1e-3)
        accum = 1

        def train_step(state, batch):
            def loss_fn(params):
                return T.forward_loss(cfg, params, batch, remat=cfg.remat)

            (loss, metrics), grads = jax.value_and_grad(
                lambda p: loss_fn(p), has_aux=True)(state["params"])
            new_params, new_opt, om = adamw_update(
                state["params"], grads, state["opt"], opt_cfg)
            return ({"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1},
                    dict(metrics, loss=loss, **om))

        step_fn = jax.jit(train_step, donate_argnums=(0,))
    else:
        prog = build_train_step(cfg, shape, mesh, rules, dec)
        step_fn = prog.jitted()

    key = jax.random.PRNGKey(0)
    state = init_train_state(cfg, key)

    ck = Checkpointer(checkpoint_dir) if checkpoint_dir else None
    start_step = 0
    if ck and resume and ck.latest_step() is not None:
        start_step = ck.latest_step()
        state = ck.restore(start_step, state)
        print(f"[resume] restored step {start_step}")

    stream = SyntheticLMStream(cfg, shape, DataConfig(seed=0))
    it = stream.prefetching(start_step=start_step)
    det = StragglerDetector()
    losses = []
    t_start = time.time()
    try:
        for i in range(start_step, steps):
            step_id, batch = next(it)
            batch = {k: jax.numpy.asarray(v) for k, v in batch.items()}
            t0 = time.time()
            with use_mesh(prog_mesh, rules):
                state, metrics = step_fn(state, batch)
            loss = float(metrics["loss"])
            det.record(0, time.time() - t0)
            losses.append(loss)
            if log_every and (i % log_every == 0 or i == steps - 1):
                print(f"step {i:5d} loss {loss:.4f} "
                      f"({(time.time() - t0) * 1e3:.0f} ms)")
            if ck and checkpoint_every and (i + 1) % checkpoint_every == 0:
                ck.save(i + 1, state)
        if ck:
            ck.save(steps, state, blocking=True)
    finally:
        it.close()

    return {"final_loss": losses[-1] if losses else float("nan"),
            "initial_loss": losses[0] if losses else float("nan"),
            "losses": losses, "steps": len(losses),
            "wall_s": time.time() - t_start}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--full", action="store_true",
                    help="use the full (non-reduced) config")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--search-first", action="store_true",
                    help="run the GA offload search before training")
    args = ap.parse_args()
    enable_compile_cache()
    out = train(args.arch, use_reduced=not args.full, steps=args.steps,
                global_batch=args.global_batch, seq_len=args.seq_len,
                checkpoint_dir=args.checkpoint_dir,
                search_first=args.search_first)
    print(f"done: loss {out['initial_loss']:.4f} -> {out['final_loss']:.4f} "
          f"in {out['wall_s']:.1f}s")


if __name__ == "__main__":
    main()
