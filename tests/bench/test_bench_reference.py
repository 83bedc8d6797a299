"""bench/reference/ agrees with the system's own forward pass at reduced
widths, in float32, for both families (the chunked RWKV scan against the
reference's token-by-token recurrence)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.reference import dense, rwkv6
from repro.configs import get_config, reduced
from repro.models import transformer as T


def _dense():
    cfg = dataclasses.replace(reduced(get_config("stablelm-1.6b")),
                              qkv_bias=True, dtype="float32")
    model = {"num_hidden_layers": cfg.num_layers, "hidden_size": cfg.d_model,
             "num_attention_heads": cfg.num_heads,
             "num_key_value_heads": cfg.num_kv_heads,
             "head_dim": cfg.resolved_head_dim, "intermediate_size": cfg.d_ff,
             "vocab_size": cfg.padded_vocab(), "rope_theta": cfg.rope_theta,
             "norm_eps": cfg.norm_eps, "use_qkv_bias": True}
    return cfg, model, dense


def _rwkv6():
    cfg = dataclasses.replace(reduced(get_config("rwkv6-1.6b")),
                              dtype="float32")
    model = {"num_hidden_layers": cfg.num_layers, "hidden_size": cfg.d_model,
             "head_size": cfg.rwkv_head_size,
             "attention_hidden_size": cfg.d_model,
             "intermediate_size": cfg.d_ff,
             "time_decay_extra_dim": cfg.rwkv_decay_rank,
             "vocab_size": cfg.padded_vocab(), "norm_eps": cfg.norm_eps}
    return cfg, model, rwkv6


@pytest.mark.parametrize("make", [_dense, _rwkv6], ids=["dense", "rwkv6"])
def test_reference_matches_the_program_forward(make):
    cfg, model, ref = make()
    params = weights.make_params(ref.layout(model), 2**40 + 3)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    tokens = jax.random.randint(jax.random.key(1), (48,), 0, model["vocab_size"])
    want = np.asarray(ref.logits_fn(model)(params, tokens))
    got, _ = T.forward(cfg, params, {"tokens": tokens[None]}, remat="none")
    got = np.asarray(got[0], np.float32)
    assert np.max(np.abs(got - want)) <= 1e-4 * np.max(np.abs(want))


@pytest.mark.parametrize("make", [_dense, _rwkv6], ids=["dense", "rwkv6"])
def test_int8_control_departs_from_float32(make):
    _, model, ref = make()
    params = weights.make_params(ref.layout(model), 5)
    tokens = jnp.arange(32) % model["vocab_size"]
    a = np.asarray(ref.logits_fn(model)(params, tokens))
    b = np.asarray(ref.logits_fn(model, int8=True)(params, tokens))
    assert 1e-3 < np.max(np.abs(a - b)) / np.max(np.abs(a)) < 0.5
