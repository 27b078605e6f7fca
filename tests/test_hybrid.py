"""The hybrid decoder (Mamba-2 layers beside grouped-query attention):
the model, the state-update kernel and the serving replica, all against
the benchmark's plain float32 reference (benchmarks/chip/
granite_reference.py) on its seeded weights, comparing logits.  Toy
widths: the rehearsal sizes of the configuration's own file."""
from __future__ import annotations

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import servekit as kit
from horovod_tpu.models import hybrid
from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import ssm
from horovod_tpu.serving.slotcache import prompt_bucket
from servekit import model_config, solo_world, tokens_of  # noqa: F401

FAMILY = "granite"
ref, granite_counts = kit.module(FAMILY), kit.module(FAMILY, "counts")
toy, params = kit.fixtures(FAMILY)


programs = kit.programs_fixture(max_seq_len=32, interpret=True)


# ------------------------------------------------------------------ the model
@pytest.mark.parametrize("length", [1, 2, 3, 7, 8, 9, 23])
def test_the_chunked_scan_agrees_with_the_sequential_reference(
        length, toy, params):
    """The whole forward pass (chunks of 8) against the reference's scan
    over positions: under the convolution's width, on and off a chunk's
    edge."""
    model = hybrid.HybridLM(model_config(toy))
    tokens = tokens_of(length, 2, length)
    got = jax.jit(model.apply)({"params": params}, tokens)
    want = kit.reference_logits(ref, params, tokens, toy)
    assert got.shape == want.shape == (2, length, 256)
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("n", [1, 3, 8, 13])
def test_prefill_of_a_padded_bucket_then_decode_through_the_cache(
        n, toy, params, programs):
    """A prompt of ``n`` in a bucket of 16 with ``lengths = n``, then 6
    decode steps in slot 2 of a four-slot cache: every logit row against
    the reference's full pass over n + 6 tokens, and the state after
    prefill equal to the reference's at ``n`` (which fails if the
    recurrence runs into the padding)."""
    row, tokens, _ = kit.prefill_then_decode(ref, toy, params, programs,
                                             n, atol=2e-6)
    with jax.default_matmul_precision("highest"):
        full = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                      params)
        _, state = jax.jit(lambda p, t: ref.mamba(
            p["layer_0"], ref.embed(p, t, toy), toy, state_at=n))(
                full, tokens[:, :n])
    stored = row["layer_0"]["mamba"]["ssm_state"]
    assert stored.shape == ssm.state_shape(1, 8, 16, 16) == (1, 1, 16, 128)
    np.testing.assert_allclose(ssm.unpack_state(stored, 8), state,
                               atol=1e-6)


def test_the_convolution_window_is_the_last_real_positions(toy, params):
    """Zeros before the start of a prompt shorter than the window."""
    config = model_config(toy, decode=True, max_seq_len=32)
    model = hybrid.HybridLM(config)
    tokens = tokens_of(5, 1, 16)
    windows = {}
    prefill = jax.jit(lambda p, t, n: hybrid.prefill(
        model, {"params": p}, t, lengths=n))
    for n in (2, 16):
        _, row = prefill(params, tokens, jnp.int32(n))
        windows[n] = row["layer_0"]["mamba"]["conv_state"][0]
    assert windows[2].shape == (3, config.conv_channels)
    assert not np.any(windows[2][0]) and np.all(np.any(windows[2][1:], -1))
    _, short = jax.jit(lambda p, t: hybrid.prefill(
        model, {"params": p}, t))(params, tokens[:, :2])
    np.testing.assert_array_equal(windows[2], short["layer_0"]["mamba"]
                                  ["conv_state"][0])
    assert np.all(np.any(windows[16], -1))


# ----------------------------------------------------------------- the kernel
def update_operands(seed=0, slots=3, heads=8, p=16, n=128):
    """The state as the equations have it, [slots, H, P, N], and the
    small operands of one step."""
    keys = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(keys[0], (slots, heads, p, n)),
            jax.random.normal(keys[1], (slots, heads, p)),
            jax.nn.softplus(jax.random.normal(keys[2], (slots, heads)) - 2),
            -jnp.exp(jax.random.uniform(keys[3], (heads,), maxval=2.7)),
            jax.random.normal(keys[4], (slots, n)),
            jax.random.normal(keys[5], (slots, n)),
            jnp.linspace(0.5, 1.5, heads))


def one_step(state, x, dt, a, b, c, d):
    """The recurrence's equations, on the state as they have it."""
    state = jnp.exp(dt * a)[..., None, None] * state \
        + (dt[..., None] * x)[..., None] * b[:, None, None, :]
    return jnp.einsum("bhpn,bn->bhp", state, c) + d[:, None] * x, state


@pytest.mark.parametrize("heads, p, block_groups",
                         [(8, 16, 1), (4, 64, 1), (4, 64, 2), (2, 128, 2)])
def test_ssm_update_agrees_with_the_equations(heads, p, block_groups):
    """The kernel, interpreted, and the plain form, both on the stored
    layout (8, 2 and 1 heads to a row of 128 lanes), against one step of
    the equations."""
    state, *small = update_operands(heads=heads, p=p)
    want_y, want_state = one_step(state, *small)
    stored = ssm.pack_state(state)
    assert stored.shape == (3, heads * p // 128, 128, 128)
    np.testing.assert_array_equal(ssm.unpack_state(stored, heads), state)
    for update in (ssm.ssm_update_plain, lambda *operands: ssm.ssm_update(
            *operands, block_groups=block_groups, interpret=True)):
        got_y, got_state = update(stored, *small)
        np.testing.assert_allclose(got_y, want_y, atol=2e-5)
        np.testing.assert_allclose(ssm.unpack_state(got_state, heads),
                                   want_state, atol=1e-6)


def test_ssm_update_writes_the_state_in_place_under_its_own_name():
    """One pallas_call named hvd.ssm_update whose first operand, the
    state, is aliased to its second result."""
    state, *small = update_operands()
    jaxpr = jax.make_jaxpr(lambda *operands: ssm.ssm_update(
        *operands, interpret=True))(ssm.pack_state(state), *small)
    call, = [eqn for eqn in jaxpr.jaxpr.eqns
             if eqn.primitive.name == "pallas_call"]
    assert tuple(call.params["input_output_aliases"]) == ((0, 1),)
    assert call.params["name"] == "hvd.ssm_update"
    assert call.invars[0].aval.shape == call.outvars[1].aval.shape


def test_off_the_tpu_the_plain_form_runs():
    state, *small = update_operands()
    jaxpr = jax.make_jaxpr(ssm.ssm_update)(ssm.pack_state(state), *small)
    assert "pallas_call" not in str(jaxpr)


# ----------------------------------------------------------------- the counts
def test_the_counts_at_the_published_widths():
    """The issue's own reckoning: 3.19 G parameters of which the tied
    matrix is 205.5 M, 75.5 MB of state a slot, 11.5 GB a decode step."""
    cfg = kit.load(FAMILY)
    assert granite_counts.layers(cfg) == (36, 4)
    assert granite_counts.matmul_params(cfg) == 3_190_292_480
    contexts = [730] * 32
    state = 32 * 36 * 64 * 64 * 128 * 4
    update = granite_counts.ssm_update_bytes_per_step(cfg, contexts)
    assert 2 * state < update < 2.02 * state
    moved = granite_counts.decode_bytes_per_step(cfg, contexts)
    assert 11.4e9 < moved < 11.6e9
    assert update / moved == pytest.approx(0.42, abs=0.01)
    flops = granite_counts.decode_flops_per_step(cfg, contexts)
    assert flops == (2 * 3_190_292_480 * 32 + 6 * 64 * 64 * 128 * 36 * 32
                     + 4 * 2048 * 4 * 730 * 32)
    # The program's own count of one token agrees with the benchmark's.
    config = model_config(cfg)
    assert config.family.decode_flops(config, 730) * 32 == flops


# ---------------------------------------------------------------- the replica
def test_requests_of_different_lengths_share_the_slot_array(
        toy, params, solo_world):
    """Seven requests over three slots, admitted into a half-decoded
    batch: every served token is the reference's best (float32: within
    1e-6 of it), and state and keys and values alike are in place."""
    rng = random.Random(29)
    prompts = [[rng.randrange(2, 256) for _ in range(n)]
               for n in (1, 2, 5, 8, 9, 13, 16)]
    new = [9, 4, 7, 12, 5, 8, 6]
    ex = kit.executor(FAMILY, model_config(toy), params)
    try:
        assert ex.family is hybrid.FAMILY
        stats = ex.stats
        assert stats["state_bytes"] < stats["cache_bytes"] \
            == stats["cache_aliased_bytes"]
        assert "kv_bytes" not in stats     # cache_bytes less state_bytes
        mamba = 3 * (8 * 16 * 16 * 4 + 3 * (128 + 32) * 4)
        assert stats["state_bytes"] == 2 * mamba
        streams = kit.serve(ex, prompts, new)
    finally:
        ex.close()
    assert [len(s) for s in streams] == new
    for prompt, served in zip(prompts, streams):
        assert kit.widest_gap(ref, params, toy, prompt, served) <= 1e-6


def test_a_reused_slot_carries_nothing_of_its_last_occupant(
        toy, params, solo_world):
    """One slot, three requests one after the other: each is served what
    a fresh cache serves it (the family's prefill and decode step on a
    cache of the request's own), and a long first occupant leaves no key,
    value or state behind."""
    rng = random.Random(3)
    prompts = [[rng.randrange(2, 256) for _ in range(n)]
               for n in (14, 3, 6)]
    new = [10, 8, 8]
    config = model_config(toy)
    ex = kit.executor(FAMILY, config, params, max_batch=1)
    try:
        streams = kit.serve(ex, prompts, new)
        model = ex.model
    finally:
        ex.close()
    family = config.family
    prefill = jax.jit(lambda p, t, n: family.prefill(
        model, {"params": p}, t, lengths=n))
    decode = jax.jit(lambda p, c, t: family.decode_step(
        model, {"params": p}, c, t))
    for prompt, count, served in zip(prompts, new, streams):
        padded = np.zeros((1, prompt_bucket(ex.cfg, len(prompt))),
                          np.int32)
        padded[0, :len(prompt)] = prompt
        logits, cache = prefill(params, jnp.asarray(padded),
                                jnp.int32(len(prompt)))
        fresh = [int(jnp.argmax(logits[0, len(prompt) - 1]))]
        while len(fresh) < count:
            logits, cache = decode(params, cache,
                                   jnp.asarray([[fresh[-1]]], jnp.int32))
            fresh.append(int(jnp.argmax(logits[0, -1])))
        assert served == fresh


def test_the_decoders_programs_are_what_they_were(solo_world):
    """TransformerLM is the protocol's first implementation: the replica
    lowers the decode and prefill programs that tfm.decode_step and
    tfm.prefill lower when called directly, as before the protocol."""
    from horovod_tpu.serving.slotcache import _sample
    ex = kit.executor(FAMILY, None, max_batch=2)
    try:
        assert ex.family is tfm.FAMILY
        model = ex.model

        def _decode_impl(params, cache, result, last_tokens, from_host):
            # The host's token where it has one newer than the last
            # step's result on the device (ISSUE 35).
            tokens = jnp.where(from_host, last_tokens, result[:2])[:, None]
            logits, cache = tfm.decode_step(model, {"params": params},
                                            cache, tokens)
            return _sample(logits[:, -1, :]), cache

        def _prefill_impl(params, tokens, n):
            logits, cache = tfm.prefill(model, {"params": params}, tokens,
                                        lengths=n)
            return _sample(logits[0, n - 1, :]), cache

        decode, args = ex.cache._decode_call(
            ex.params, ex._last_tokens, ex._token_on_host)
        assert decode is ex.cache._decode_jit
        assert decode.lower(*args).as_text() \
            == jax.jit(_decode_impl, donate_argnums=1).lower(
                *args).as_text()
        prompt = (jnp.zeros((1, 8), jnp.int32), jnp.int32(3))
        assert ex.cache._prefill_jit.lower(ex.params, *prompt).as_text() \
            == jax.jit(_prefill_impl).lower(ex.params, *prompt).as_text()
        assert ex.stats["state_bytes"] == 0
        assert ex.stats["cache_bytes"] > 0 and "kv_bytes" not in ex.stats
    finally:
        ex.close()


@pytest.mark.parametrize("family", ["transformer", "hybrid"])
def test_flops_per_token_come_from_the_model_family(
        family, toy, params, solo_world, monkeypatch):
    """horovod_serve_flops_per_token is the family's own count at the
    step's mean context, for the decoder and for the hybrid model."""
    from horovod_tpu import telemetry
    from horovod_tpu.telemetry import perfmodel
    monkeypatch.setenv("HOROVOD_METRICS", "on")
    registry = telemetry.configure()
    try:
        ex = kit.executor(FAMILY, *((model_config(toy), params)
                                    if family == "hybrid" else (None,)))
        try:
            kit.serve(ex, [[5, 6, 7, 8]], [4])
            config = ex.model.cfg
        finally:
            ex.close()
        seen = {entry["name"]: entry["value"]
                for entry in registry.snapshot()["metrics"]
                if "value" in entry}
        # After the last step: a prompt of 4 and 3 decoded tokens.
        count = perfmodel.hybrid_decode_flops if family == "hybrid" \
            else perfmodel.transformer_decode_flops
        assert seen["horovod_serve_flops_per_token"] == count(config, 7)
        assert count(config, 7) > count(config, 0) > 0
    finally:
        monkeypatch.delenv("HOROVOD_METRICS")
        telemetry.configure()


# --------------------------------------------- the benchmark's own comparison
@pytest.mark.parametrize("seed", [3, 2147483659, 2000000011])
def test_the_cells_control_in_int8_comes_out_not_correct(seed, monkeypatch,
                                                         capsys):
    """``--check control`` of the new cell at its rehearsal sizes: the
    served tokens stay inside the toy limits, and the tokens that the
    reference computed in int8 (the state left in float32) puts first do
    not."""
    seen = kit.check_control(monkeypatch, capsys, FAMILY, seed)
    assert seen["code"] == 0 and seen["ok"] and not seen["problems"]
    assert seen["served_tokens"] > 400
    assert any(seen[key] <= limit < seen["control_" + key]
               for key, limit in seen["limits"].items())
