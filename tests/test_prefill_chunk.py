"""Chunked prefill at admission (``transformer.prefill_chunk``, fed by
``ServingEngine._prefill_prompt``) at a tiny size on the CPU.

The program: feeding all but the last prompt token in chunks, then the last
through ``decode_step``, gives the logits that feeding every prompt token
through ``decode_step`` gives, writes only the slot's prompt rows, and
leaves every other slot's rows and position as they were. The engine: a
dense model's prompt takes ``ceil((L-1)/C)`` chunk programs and its first
token comes out of the admitting step; recurrent and expert families feed
a token a step, as before."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models as M
from repro.configs import get_config, reduced
from repro.models import transformer as T
from repro.runtime import Request, ServingEngine

CHUNK = 8  # small enough that a 14-token prompt takes two chunks
SLOT = 1
decode_step = jax.jit(T.decode_step, static_argnums=0)
prefill_chunk = jax.jit(T.prefill_chunk, static_argnums=0)


@pytest.fixture(scope="module")
def stablelm():
    """reduced stablelm-1.6b with its q/k/v bias, as the benchmark runs it;
    the biases drawn (they start at zero) so that they count."""
    cfg = dataclasses.replace(reduced(get_config("stablelm-1.6b")),
                              qkv_bias=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    attn = params["layers"]["attn"]
    for key, name in zip(jax.random.split(jax.random.PRNGKey(1), 3),
                         ("bq", "bk", "bv")):
        attn[name] = (0.5 * jax.random.normal(key, attn[name].shape)
                      ).astype(attn[name].dtype)
    return cfg, params


def _base_state(cfg, params, live_neighbours: bool):
    """3 slots x 48 with slot ``SLOT`` just reset for admission; with
    ``live_neighbours`` every slot first decoded 5 tokens, so the slots
    beside it hold rows and positions, and it holds its last occupant's
    rows."""
    state = T.init_decode_state(cfg, 3, 48)
    if live_neighbours:
        rng = np.random.default_rng(7)
        for _ in range(5):
            _, state = decode_step(
                cfg, params, state,
                jnp.asarray(rng.integers(1, cfg.vocab_size, 3), jnp.int32))
    return T.reset_decode_slots(cfg, state, jnp.arange(3) == SLOT)


@pytest.mark.parametrize("live_neighbours", [False, True])
@pytest.mark.parametrize("length", [6, CHUNK + 1, 14])
def test_chunked_prompt_gives_the_logits_of_one_token_feeding(
        stablelm, length, live_neighbours):
    """Prompts whose first L - 1 tokens are below, equal to and above one
    chunk: the logits after the last prompt token match one-token feeding,
    and no row or position outside the slot's prompt rows moves."""
    cfg, params = stablelm
    prompt = np.random.default_rng(length).integers(
        1, cfg.vocab_size, length).tolist()
    base = _base_state(cfg, params, live_neighbours)

    def tokens(t):  # the neighbours get a token of their own each step
        return jnp.asarray([7, t, 9], jnp.int32)

    ref = base
    for t in prompt:
        ref_logits, ref = decode_step(cfg, params, ref, tokens(t))

    state, n = base, length - 1
    for start in range(0, n, CHUNK):
        part = prompt[start:min(start + CHUNK, n)]
        chunk = np.zeros((CHUNK,), np.int32)
        chunk[:len(part)] = part
        state = prefill_chunk(cfg, params, state, SLOT, jnp.asarray(chunk),
                                start, len(part))

    others = np.arange(3) != SLOT
    np.testing.assert_array_equal(np.asarray(state["pos"])[others],
                                  np.asarray(base["pos"])[others])
    assert int(state["pos"][SLOT]) == n
    for leaf in ("k", "v"):
        got, was = np.asarray(state["kv"][leaf]), np.asarray(base["kv"][leaf])
        # bit for bit: the neighbours, and the slot's rows past the prompt
        np.testing.assert_array_equal(got[:, others], was[:, others])
        np.testing.assert_array_equal(got[:, SLOT, n:], was[:, SLOT, n:])
        np.testing.assert_allclose(
            got[:, SLOT, :n].astype(np.float32),
            np.asarray(ref["kv"][leaf])[:, SLOT, :n].astype(np.float32),
            rtol=2 ** -7, atol=2 ** -7)

    logits, _ = decode_step(cfg, params, state, tokens(prompt[-1]))
    want = np.asarray(ref_logits[SLOT], np.float32)
    got = np.asarray(logits[SLOT], np.float32)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2 ** -7 * np.abs(want).max())
    assert int(np.argmax(got)) == int(np.argmax(want))


@pytest.mark.parametrize("arch,window", [
    ("rwkv6-1.6b", 0), ("granite-4.0-h-small", 0), ("mixtral-8x7b", 0),
    ("stablelm-1.6b", 32)])
def test_no_chunk_path_for_state_experts_or_a_window(arch, window):
    cfg = get_config(arch)
    if window:
        cfg = dataclasses.replace(cfg, sliding_window=window)
    assert not T.prefill_chunk_supported(cfg)


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "llama3.2-3b",
                                  "granite-20b", "qwen1.5-110b"])
def test_dense_models_take_the_chunk_path(arch):
    assert T.prefill_chunk_supported(get_config(arch))


def _requests():
    """Prompts of 1 (no chunk), 5, 9 (one chunk of 8) and 14 (two) tokens."""
    return [Request(rid=i, prompt=[1 + (3 * i + j) % 29 for j in range(n)],
                    max_new_tokens=3 + i % 2)
            for i, n in enumerate((5, 14, 1, 9))]


def _serve(cfg, params, reqs):
    """One slot, so requests run one after another; records, per request,
    whether its first token came out of the step that admitted it."""
    eng = ServingEngine(cfg, params, slots=1, max_len=32)
    eng.prefill_len = CHUNK
    for r in reqs:
        eng.submit(r)
    eng.stream_open()
    first_in_admitting_step = {}
    while True:
        queued = [r for r in reqs if r.status == "queued"]
        if eng.stream_step() is None:
            break
        for r in queued:
            if r.status != "queued":
                first_in_admitting_step[r.rid] = len(r.output) == 1
    eng.stream_close()
    return eng, first_in_admitting_step


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "rwkv6-1.6b",
                                  "granite-4.0-h-small"])
def test_engine_feeds_dense_prompts_in_chunks_and_others_a_token_a_step(
        stablelm, arch):
    if arch == "stablelm-1.6b":
        cfg, params = stablelm
    else:
        cfg = reduced(get_config(arch))
        params = M.init_params(cfg, jax.random.PRNGKey(0))
    reqs = _requests()
    eng, first = _serve(cfg, params, reqs)
    s = eng.stats
    prompt = [len(r.prompt) for r in reqs]
    new = [r.max_new_tokens for r in reqs]
    assert all(r.finish_reason == "max_new_tokens" for r in reqs)
    assert s.prefill_tokens == sum(prompt)
    assert s.decode_tokens == sum(new) - len(reqs)
    if T.prefill_chunk_supported(cfg):
        assert s.prefill_chunks == sum(math.ceil((n - 1) / CHUNK)
                                       for n in prompt) == 4
        assert s.chunked_prompt_tokens == sum(n - 1 for n in prompt)
        assert s.steps == sum(new)
        assert all(first.values()) and len(first) == len(reqs)
    else:
        assert s.prefill_chunks == s.chunked_prompt_tokens == 0
        assert s.steps == sum(p + n - 1 for p, n in zip(prompt, new))
        assert [first[r.rid] for r in reqs] == [len(r.prompt) == 1
                                                for r in reqs]


def test_chunked_engine_serves_the_tokens_of_one_token_feeding(
        stablelm, monkeypatch):
    """The same requests, two slots, the default chunk and one of 8: the
    outputs of one-token feeding (the chunk path switched off here)."""
    cfg, params = stablelm

    def outputs(chunk):
        reqs = _requests()
        eng = ServingEngine(cfg, params, slots=2, max_len=32)
        eng.prefill_len = chunk or eng.prefill_len
        for r in reqs:
            eng.submit(r)
        eng.run()
        return [r.output for r in reqs], eng.stats.prefill_chunks

    chunked, default = outputs(CHUNK), outputs(None)
    monkeypatch.setattr(T, "prefill_chunk_supported", lambda cfg: False)
    one_token, none = outputs(None)
    assert chunked[0] == default[0] == one_token
    assert (chunked[1], default[1], none) == (4, 3, 0)
