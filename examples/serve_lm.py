"""Serving example: batched requests through the slot-stream engine
(continuous batching with per-slot position streams), across three
architecture families (dense, SSM, MoE) with one code path. Each engine
carries a destination-priced placement, so every served request reports
which engine and offload destination billed it.

    PYTHONPATH=src python examples/serve_lm.py
"""
import sys

sys.path.insert(0, "src")

import jax

from repro.configs import get_config, reduced
from repro import models as M
from repro.runtime import Request, ServingEngine, static_placements
from repro.runtime.placement import DEFAULT_MESH_OPTIONS


def main():
    for arch in ("llama3.2-3b", "rwkv6-1.6b", "mixtral-8x7b"):
        cfg = reduced(get_config(arch))
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        engine = ServingEngine(cfg, params, slots=4, max_len=64,
                               name=f"{arch}-engine")
        engine.reconfigure(static_placements(arch, DEFAULT_MESH_OPTIONS[0]))
        for i in range(6):
            engine.submit(Request(rid=i, prompt=[1 + i, 7, 3, 2],
                                  max_new_tokens=6))
        done = engine.run()
        s = engine.stats
        print(f"{arch:<16} served={len(done)} steps={s.steps} "
              f"occupancy={s.occupancy:.2f} "
              f"decode_tokens={s.decode_tokens} "
              f"sample_output={done[0].output}")
        for r in done:
            print(f"    rid={r.rid} served_by={r.served_by} "
                  f"destination={r.destination}")


if __name__ == "__main__":
    main()
