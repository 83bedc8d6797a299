"""The moe_hybrid family (granite-4.0-h-small) at a tiny size on the CPU:
the expert layer that holds a share of the experts, and a slot's two kinds
of decode state through reset, admission, extract and restore."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models as M
from repro.configs import get_config, reduced
from repro.models import moe as moe_mod
from repro.models import transformer as T
from repro.runtime import Request, ServingEngine

# two periods of (Mamba2, attention), GQA, 4 of 8 experts held
TINY = dataclasses.replace(reduced(get_config("granite-4.0-h-small")),
                           num_layers=4, num_kv_heads=2, num_experts=8,
                           experts_held=4, experts_per_token=2)


@pytest.fixture(scope="module")
def tiny():
    return TINY, M.init_params(TINY, jax.random.PRNGKey(0))


def _expert_weights(key, cfg, n):
    """One layer's router over every expert and ``n`` experts, stacked
    over one layer as ``held_experts`` takes them."""
    kr, ki, ko = jax.random.split(key, 3)
    d, f = cfg.d_model, cfg.d_ff
    return {"router": jax.random.normal(kr, (1, d, cfg.num_experts))
            * d ** -0.5,
            "w_in": jax.random.normal(ki, (1, n, d, 2 * f)) * d ** -0.5,
            "w_out": jax.random.normal(ko, (1, n, f, d)) * f ** -0.5}


def test_held_expert_shares_add_up_to_the_uncut_layer():
    """Expert parallelism over 8 chips, 2 experts each: the parts that the
    8 shares compute, with the shared expert counted once, add up to the
    layer that holds all 16 experts."""
    cfg = dataclasses.replace(TINY, dtype="float32", num_experts=16,
                              experts_per_token=4, experts_held=0)
    key = jax.random.PRNGKey(3)
    p = _expert_weights(key, cfg, 16)
    shared = {"w_in": jax.random.normal(key, (cfg.d_model,
                                              2 * cfg.shared_expert_ff)),
              "w_out": jax.random.normal(key, (cfg.shared_expert_ff,
                                               cfg.d_model)) * 0.05}
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 5, cfg.d_model))
    uncut, hits = moe_mod.held_experts(cfg, p, x)
    uncut = uncut + moe_mod.shared_expert(cfg, shared, x)
    assert np.all(np.asarray(hits) == 4)  # every choice is held here

    share_cfg = dataclasses.replace(cfg, experts_held=2)
    total, share_hits = moe_mod.shared_expert(cfg, shared, x), 0
    for b in range(8):
        p_b = {"router": p["router"], "w_in": p["w_in"][:, 2 * b:2 * b + 2],
               "w_out": p["w_out"][:, 2 * b:2 * b + 2]}
        y, h = moe_mod.held_experts(share_cfg, p_b, x, first=2 * b)
        total, share_hits = total + y, share_hits + h
    assert np.all(np.asarray(share_hits) == 4)
    # float32; only the order of the sum over experts differs
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               rtol=1e-5, atol=1e-5)


def test_grouped_dispatch_drops_no_token_where_capacity_dispatch_does():
    """Every token of a row picks the same experts: the row-local capacity
    dispatch (``moe_apply``) keeps ``capacity`` of them per expert and drops
    the rest, while the grouped layer computes every choice, as the plain
    sum over each token's chosen experts does."""
    cfg = dataclasses.replace(reduced(get_config("mixtral-8x7b")),
                              dtype="float32", experts_held=0)
    key = jax.random.PRNGKey(5)
    p = _expert_weights(key, cfg, cfg.num_experts)
    f = cfg.d_ff
    capacity_p = {"router": p["router"][0], "w_gate": p["w_in"][0, ..., :f],
                  "w_up": p["w_in"][0, ..., f:], "w_down": p["w_out"][0]}
    s = 16
    x = jnp.broadcast_to(jax.random.normal(key, (1, 1, cfg.d_model)),
                         (1, s, cfg.d_model))
    assert moe_mod.capacity(cfg, s) < s  # so the capacity path drops

    weights, ids, _ = moe_mod.route(cfg, capacity_p, x)
    want = jnp.zeros_like(x)
    for c in range(cfg.experts_per_token):
        e = ids[..., c]
        h = jnp.einsum("bsd,bsdf->bsf", x, p["w_in"][0][e])
        h = jax.nn.silu(h[..., :f]) * h[..., f:]
        want = want + weights[..., c:c + 1] * jnp.einsum(
            "bsf,bsfd->bsd", h, p["w_out"][0][e])

    grouped, hits = moe_mod.held_experts(cfg, p, x)
    capped, _ = moe_mod.moe_apply(cfg, capacity_p, x)
    assert np.all(np.asarray(hits) == cfg.experts_per_token)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    dropped = np.max(np.abs(np.asarray(capped - want)), axis=-1)[0]
    assert np.all(dropped[:moe_mod.capacity(cfg, s)] < 1e-5)
    assert np.all(dropped[moe_mod.capacity(cfg, s):] > 1e-2)


def test_reset_slot_decodes_exactly_like_a_fresh_slot(tiny):
    """A slot reset by ``reset_decode_slots`` and admitted again decodes
    bit for bit like slot 0 of a fresh state, though its Mamba and KV
    stacks still hold the last request's values; its neighbour's stream
    goes on as if nothing happened."""
    cfg, params = tiny
    step = jax.jit(lambda s, t: T.decode_step(cfg, params, s, t))
    st = M.init_decode_state(cfg, 2, 16)
    for t in (3, 5, 7):
        _, st = step(st, jnp.array([t, t + 1], jnp.int32))
    st_reset = M.reset_decode_slots(cfg, st, jnp.array([True, False]))
    fresh = M.init_decode_state(cfg, 2, 16)
    for t in (2, 4, 6):
        la, st_reset = step(st_reset, jnp.array([t, 9], jnp.int32))
        lf, fresh = step(fresh, jnp.array([t, 9], jnp.int32))
        lc, st = step(st, jnp.array([t, 9], jnp.int32))
        np.testing.assert_array_equal(np.asarray(la[0]), np.asarray(lf[0]))
        np.testing.assert_array_equal(np.asarray(la[1]), np.asarray(lc[1]))


def test_extract_restore_round_trips_both_kinds_of_state(tiny):
    """A slot's Mamba state (conv and SSM) and KV rows leave by
    ``extract_decode_slot`` and come back by ``restore_decode_slot`` into
    another state bit for bit, and decode on identically."""
    cfg, params = tiny
    step = jax.jit(lambda s, t: T.decode_step(cfg, params, s, t))
    st = M.init_decode_state(cfg, 3, 16)
    for t in (3, 5, 7, 11):
        _, st = step(st, jnp.array([t, t + 1, t + 2], jnp.int32))
    leaves, pos = T.extract_decode_slot(cfg, st, 1)
    assert set(leaves) == {"mamba", "attn", "expert_hits"} and pos == 4
    other = M.init_decode_state(cfg, 3, 16)
    other = T.restore_decode_slot(cfg, other, 2, leaves, pos)
    back, back_pos = T.extract_decode_slot(cfg, other, 2)
    assert back_pos == pos
    for a, b in zip(jax.tree.leaves(leaves), jax.tree.leaves(back)):
        np.testing.assert_array_equal(a, b)
    la, _ = step(st, jnp.array([1, 13, 1], jnp.int32))
    lb, _ = step(other, jnp.array([1, 1, 13], jnp.int32))
    np.testing.assert_array_equal(np.asarray(la[1]), np.asarray(lb[2]))


def test_engine_counts_expert_choices_of_active_slots(tiny):
    """With every expert held, each active slot's token lands all its
    ``experts_per_token`` choices on held experts in every layer, and idle
    slots count nothing; ``moe_layer_steps`` is steps times layers."""
    cfg, params = tiny
    cfg = dataclasses.replace(cfg, experts_held=0)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, slots=3, max_len=16)
    for i in range(4):
        eng.submit(Request(rid=i, prompt=[1 + i, 2, 3], max_new_tokens=2 + i))
    done = eng.run()
    assert len(done) == 4
    s = eng.stats
    assert s.active_slot_steps < s.slot_steps  # some steps had idle slots
    assert s.moe_layer_steps == s.steps * cfg.num_layers
    assert s.expert_assignments == (s.active_slot_steps * cfg.num_layers
                                    * cfg.experts_per_token)
