"""MiMo-V2.5 on the hybrid decoder: window layers with a sink and a ring
in the cache beside global layers whose few key-value heads lie in the
lanes, keys wider than values, rotary positions on part of a head, a
router with a correction bias; the model through the slot cache and the
replica, all against the benchmark's plain float32 reference
(benchmarks/chip/mimo_v2_reference.py) on its seeded weights, comparing
logits.  Toy widths: the rehearsal sizes of the configuration's own
file (a window of 8, keys 24 and values 16 wide, 8 rotary channels)."""
from __future__ import annotations

import dataclasses
import functools
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import servekit as kit
from horovod_tpu.models import hybrid, kvcache, moe
from horovod_tpu.ops import decode_attention as da
from servekit import harness, model_config, solo_world, tokens_of  # noqa: F401
from test_decode_attention import pallas_calls

FAMILY = "mimo"
ref, mimo_v2_counts = kit.module(FAMILY), kit.module(FAMILY, "counts")
toy, params = kit.fixtures(FAMILY)
slot_programs = kit.slot_programs_fixture()
WINDOW = 8


# ------------------------------------------------- the attention's three forms
def operands(seed: int, b: int, t: int, s: int, h: int, kv: int, dk: int,
             dv: int, dtype=jnp.float32):
    keys = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(keys[0], (b, t, h, dk), dtype),
            jax.random.normal(keys[1], (b, s, kv, dk), dtype),
            jax.random.normal(keys[2], (b, s, kv, dv), dtype),
            jax.random.normal(keys[3], (h,), jnp.float32))


def softmax_by_hand(q, k, v, positions, scale, sink=None, window=0):
    """One query head at a time, the sink an explicit exponential in the
    denominator."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    b, t, h, _ = q.shape
    group = h // k.shape[2]
    out = np.zeros((b, t, h, v.shape[-1]))
    for row in range(b):
        for i in range(t):
            p = int(positions[row, i])
            lo = max(0, p - window + 1) if window else 0
            for head in range(h):
                s = k[row, lo:p + 1, head // group] @ q[row, i, head] * scale
                top = max(s.max(), sink[head]) if sink is not None \
                    else s.max()
                e = np.exp(s - top)
                den = e.sum() + (np.exp(sink[head] - top)
                                 if sink is not None else 0.0)
                out[row, i, head] = e @ v[row, lo:p + 1, head // group] / den
    return out


@pytest.mark.parametrize("window", [0, 5])
@pytest.mark.parametrize("sinks", [False, True])
def test_the_plain_form_takes_a_sink_a_window_and_narrower_values(window,
                                                                  sinks):
    q, k, v, sink = operands(1, 2, 6, 16, 4, 2, 24, 16)
    sink = np.asarray(sink) if sinks else None
    positions = jnp.asarray([[3, 4, 5, 6, 7, 8], [9, 10, 11, 12, 13, 14]])
    got = da.attend_plain(q, k, v, positions, 0.2,
                          None if sink is None else jnp.asarray(sink), window)
    assert got.shape == (2, 6, 4, 16) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, softmax_by_hand(
        q, k, v, np.asarray(positions), 0.2, sink, window), atol=2e-6)


@pytest.mark.parametrize("t, block", [(5, 8), (8, 8), (19, 4), (32, 8),
                                      (37, 16)])
@pytest.mark.parametrize("window, sinks", [(0, False), (0, True), (3, True),
                                           (8, True), (11, False)])
def test_the_blocked_prefill_is_the_plain_form_over_its_own_keys(
        t, block, window, sinks):
    """Whole blocks and a ragged last one, windows shorter than a block,
    as long, and longer: a row that sees nothing of an earlier block is
    wiped by its own."""
    q, k, v, sink = operands(t, 2, t, t, 4, 2, 24, 16)
    sink = sink if sinks else None
    got = da.attend_blocked(q, k, v, 0.2, window=window, sink=sink,
                            block=block)
    want = da.attend_plain(q, k, v, jnp.arange(t)[None, :], 0.2, sink,
                           window)
    assert got.shape == want.shape == (2, t, 4, 16)
    np.testing.assert_allclose(got, want, atol=3e-6, rtol=3e-6)


LENGTHS = {"ragged": (1, 7, 8, 9, 33, 64), "one": (1,) * 6,
           "full": (64,) * 6}


def last_row_step(k, v, lens):
    """A decode step whose row is each slot's last live one, as the
    lanes kernel takes it (ISSUE 39: the kernel writes it): ``(new_k,
    new_v, lengths, at)``, the rows out of ``k`` and ``v`` [B, S, KV, D]
    and merged."""
    at = jnp.asarray(lens, jnp.int32) - 1
    return (*(jnp.take_along_axis(x, at[:, None, None, None], axis=1)
              .reshape(len(at), 1, -1) for x in (k, v)), at + 1, at)


@pytest.mark.parametrize("lengths", sorted(LENGTHS))
@pytest.mark.parametrize("sinks", [False, True])
@pytest.mark.parametrize("dk, dv, dtype", [(24, 16, jnp.bfloat16),
                                           (24, 16, jnp.float32),
                                           (192, 128, jnp.bfloat16)])
def test_the_lanes_kernel_interpreted_agrees_with_the_plain_form(
        dk, dv, dtype, sinks, lengths):
    """hvd.decode_attend on leaves with the heads in the lanes, 4
    key-value heads of 16 query heads each, at the toy widths and at
    MiMo's: ragged lengths, a slot of one position, full slots; dead
    positions hold NaN and must not reach the result."""
    heads, kv, s, block = 64, 4, 64, 16
    lens = np.asarray(LENGTHS[lengths], np.int32)
    q, k, v, sink = operands(7, len(lens), 1, s, heads, kv, dk, dv, dtype)
    sink = sink if sinks else None
    # (and so does the step's own row, which the kernel is handed)
    dead = np.arange(s)[None, :, None, None] \
        >= lens[:, None, None, None] - 1
    merged = [jnp.where(dead, jnp.nan, x).reshape(len(lens), s, -1)
              for x in (k, v)]
    step = last_row_step(k, v, lens)
    got, *written = da._decode_attend_lanes(
        q, *merged, *step, sink, 0.11, block=block, interpret=True)
    want = da.attend_plain(q, k, v, jnp.asarray(lens)[:, None] - 1, 0.11,
                           sink)
    assert got.shape == (len(lens), 1, heads, dv)
    np.testing.assert_allclose(got, want, atol=5e-6, rtol=5e-6)
    for mine, leaf, row in zip(written, merged, step):
        np.testing.assert_array_equal(                    # NaN equals NaN
            np.asarray(mine, np.float32),
            np.asarray(da.write_rows(leaf, row, step[3]), np.float32))


def test_the_entry_point_reads_lanes_leaves_in_the_block_the_rule_gives(
        monkeypatch):
    """MiMo's global leaves, [B, S, 4 x 192] and [B, S, 4 x 128] in
    bfloat16: ``decode_attend`` interpreted goes through the lanes kernel
    in ``block_positions``' block, with the sink; elsewhere the plain
    form reads the same leaves; the rule leaves the 7B's and the toy's
    leaves where they were, and takes granite's value heads of 64, two
    to a lane tile."""
    assert da.lanes_layout(4, 192, 128, jnp.bfloat16)
    assert da.lanes_layout(8, 128, 128, jnp.bfloat16)         # Solar
    assert da.lanes_layout(8, 192, 128, jnp.bfloat16)         # the rings
    assert not da.lanes_layout(16, 128, 128, jnp.bfloat16)    # a whole tile
    assert da.lanes_layout(8, 64, 64, jnp.bfloat16)           # granite
    assert not da.lanes_layout(8, 64, 48, jnp.bfloat16)       # 128 % 48
    assert not da.lanes_layout(2, 64, 32, jnp.bfloat16)       # 64 lanes
    assert not da.lanes_layout(4, 192, 128, jnp.float32)
    assert not da.lanes_layout(1, 24, 16, jnp.bfloat16)       # the toy
    assert da.block_positions(12288, 4, 192, jnp.bfloat16, 128) == 512
    assert da.kernel_block((64, 12288, 768), jnp.bfloat16, True,
                           (64, 12288, 512)) == 512
    # Such heads in the sublanes would be padded: no kernel for them.
    assert da.kernel_block((64, 12288, 4, 192), jnp.bfloat16, True,
                           (64, 12288, 4, 128)) == 0
    assert da.kernel_block((64, 128, 8, 192), jnp.bfloat16, True,
                           (64, 128, 8, 128)) == 0
    assert da.kernel_block((64, 128, 8 * 192), jnp.bfloat16, True,
                           (64, 128, 8 * 128)) == 128         # the rings
    lens = jnp.asarray([5, 40, 64], jnp.int32)
    q, k, v, sink = operands(3, 3, 1, 64, 64, 4, 192, 128, jnp.bfloat16)
    monkeypatch.setattr(da, "_BLOCK_BYTES", 16 * 4 * 192 * 2)
    calls = []
    real = da._decode_attend_lanes
    monkeypatch.setattr(da, "_decode_attend_lanes",
                        lambda *a, **kw: calls.append(kw) or real(*a, **kw))
    step = last_row_step(k, v, lens)
    # Zeros where the step's row goes: it is the kernel's to write.
    merged = [da.write_rows(x.reshape(3, 64, -1), jnp.zeros_like(row),
                            step[3]) for x, row in zip((k, v), step)]
    got, *written = da.decode_attend(q, *merged, *step, 0.07, sink,
                                     interpret=True)
    assert calls == [{"block": 16, "interpret": True,
                      "name": "hvd.decode_attend"}]
    want = da.attend_plain(q, k, v, lens[:, None] - 1, 0.07, sink)
    np.testing.assert_allclose(got, want, atol=5e-6, rtol=5e-6)
    plain, *plainly = da.decode_attend(q, *merged, *step, 0.07,
                                       sink)                    # a CPU
    assert len(calls) == 1
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(want))
    for mine, theirs, whole in zip(written, plainly, (k, v)):
        np.testing.assert_array_equal(mine, theirs)
        np.testing.assert_array_equal(mine, whole.reshape(3, 64, -1))


def test_the_lanes_kernel_carries_the_decode_kernels_name():
    q, k, v, _ = operands(3, 2, 1, 32, 64, 4, 192, 128, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda *a: da._decode_attend_lanes(
        *a, None, 0.1, block=16, interpret=True))(
        q, k.reshape(2, 32, -1), v.reshape(2, 32, -1),
        *last_row_step(k, v, [3, 30]))
    call, = pallas_calls(jaxpr.jaxpr)
    assert "hvd.decode_attend" in str(call.params["name"]) \
        or "hvd.decode_attend" in str(call.params.get("name_and_src_info"))


# ------------------------------------------------------------------ the ring
def a_window_layer(toy, **overrides):
    cfg = model_config(toy, **overrides)
    return cfg, hybrid.GroupedAttention(cfg, windowed=True)


@pytest.mark.parametrize("n, bucket", [(3, 8), (8, 8), (9, 16), (21, 32),
                                       (32, 32)])
def test_a_window_layers_ring_is_a_full_leaf_under_a_window_mask(n, bucket,
                                                                 toy):
    """One window layer, two ways: through the cache, a right-padded
    prompt of ``n`` in ``bucket`` and then 3 x 8 + 5 decode steps, the
    ring going round three times and more; and over the whole sequence
    at once, every key kept and the window a mask.  The ring never holds
    the padding."""
    steps = 3 * WINDOW + 5
    cfg, plain = a_window_layer(toy)
    _, cached = a_window_layer(toy, decode=True, max_seq_len=128)
    x = jax.random.normal(jax.random.key(n), (1, n + steps, 64))
    own = plain.init(jax.random.key(1), x)["params"]
    own = {**own, "sink": jax.random.normal(jax.random.key(2), (4,))}
    want = plain.apply({"params": own}, x)
    padded = jnp.zeros((1, bucket, 64)).at[:, :n].set(x[:, :n]) \
        .at[:, n:].set(7.0)                  # padding that would be seen
    got, mut = cached.apply({"params": own}, padded, jnp.int32(n),
                            mutable=["cache"])
    cache = kvcache._with_cache_index(mut["cache"], n)
    assert cache["ring_key"].shape == (1, WINDOW, 2, 24)
    assert cache["ring_value"].shape == (1, WINDOW, 2, 16)
    np.testing.assert_allclose(got[:, :n], want[:, :n], atol=3e-6)
    for at in range(n, n + steps):
        out, mut = cached.apply({"params": own, "cache": cache},
                                x[:, at:at + 1], mutable=["cache"])
        cache = mut["cache"]
        np.testing.assert_allclose(out[:, 0], want[:, at], atol=3e-6)
    assert int(cache["cache_index"][0]) == n + steps
    with pytest.raises(ValueError, match="whole prompt or one token"):
        cached.apply({"params": own, "cache": cache}, x[:, :2],
                     mutable=["cache"])


def test_lanes_leaves_and_long_prompts_through_the_cache(monkeypatch):
    """What the toy widths never take: bfloat16 leaves of 2 key-value
    heads whose value heads are whole lanes lie ``[B, S, KV x D]`` (keys
    256 and values 128 wide), and a prefill whose scores would pass
    ``PLAIN_PREFILL_BYTES`` attends in blocks over its own keys; both
    against the same layer without a cache."""
    cfg = hybrid.HybridConfig(
        d_model=64, num_heads=4, num_kv_heads=2, attn_head_dim=256,
        attn_value_dim=128, attn_rotary_dim=32, attn_value_scale=0.5,
        attention_multiplier=0.09, layer_types=("attention",),
        dtype=jnp.bfloat16)
    plain = hybrid.GroupedAttention(cfg)
    cached = hybrid.GroupedAttention(dataclasses.replace(
        cfg, decode=True, max_seq_len=32))
    x = jax.random.normal(jax.random.key(0), (2, 20, 64), jnp.bfloat16)
    own = plain.init(jax.random.key(1), x)["params"]
    want = plain.apply({"params": own}, x).astype(jnp.float32)
    monkeypatch.setattr(kvcache, "PLAIN_PREFILL_BYTES", 1024)
    blocked = []
    real = kvcache.attend_blocked
    monkeypatch.setattr(kvcache, "attend_blocked", lambda *a, **kw:
                        blocked.append(kw) or real(*a, **{**kw, "block": 8}))
    got, mut = cached.apply({"params": own}, x[:, :16], mutable=["cache"])
    assert len(blocked) == 1
    cache = mut["cache"]
    assert cache["cached_key"].shape == (2, 32, 2 * 256)
    assert cache["cached_value"].shape == (2, 32, 2 * 128)
    rows = [got.astype(jnp.float32)]
    for at in range(16, 20):
        out, mut = cached.apply({"params": own, "cache": cache},
                                x[:, at:at + 1], mutable=["cache"])
        cache = mut["cache"]
        rows.append(out.astype(jnp.float32))
    np.testing.assert_allclose(jnp.concatenate(rows, 1), want, atol=0.03)


@pytest.mark.parametrize("n, bucket", [(5, 8), (8, 8), (13, 16), (27, 32)])
def test_a_ring_in_the_lanes_through_the_kernel(n, bucket, monkeypatch):
    """A window layer of 8 bfloat16 key-value heads (keys 192 and values
    128 wide, a sink, a window of 8): its rings lie ``[B, 8, 8 x D]``.
    A right-padded prompt shorter than the window, as long and longer
    fills them in rows of the merged width; then 2 x 8 + 3 decode steps,
    past two wraps, each through the lanes kernel (interpreted) under
    the ring's own name with ``lengths = min(index + 1, window)``;
    against the same layer over the whole sequence, the window a mask."""
    steps = 2 * WINDOW + 3
    cfg = hybrid.HybridConfig(
        d_model=64, num_heads=16, num_kv_heads=4, window_kv_heads=8,
        attn_head_dim=192, attn_value_dim=128, attn_rotary_dim=64,
        attention_multiplier=0.07, window=WINDOW, window_sink=True,
        layer_types=("window",), dtype=jnp.bfloat16)
    plain = hybrid.GroupedAttention(cfg, windowed=True)
    cached = hybrid.GroupedAttention(dataclasses.replace(
        cfg, decode=True, max_seq_len=64), windowed=True)
    x = jax.random.normal(jax.random.key(n), (2, n + steps, 64),
                          jnp.bfloat16)
    own = plain.init(jax.random.key(1), x)["params"]
    own = {**own, "sink": jax.random.normal(jax.random.key(2), (16,))}
    want = plain.apply({"params": own}, x).astype(jnp.float32)
    calls = []
    real = da._decode_attend_lanes
    monkeypatch.setattr(da, "_decode_attend_lanes",
                        lambda *a, **kw: calls.append((a[5], kw))
                        or real(*a, **kw))
    monkeypatch.setattr(kvcache, "decode_attend", functools.partial(
        da.decode_attend, interpret=True))
    padded = jnp.full((2, bucket, 64), 7.0, jnp.bfloat16) \
        .at[:, :n].set(x[:, :n])             # padding that would be seen
    got, mut = cached.apply({"params": own}, padded, jnp.int32(n),
                            mutable=["cache"])
    cache = kvcache._with_cache_index(mut["cache"], n)
    assert cache["ring_key"].shape == (2, WINDOW, 8 * 192)
    assert cache["ring_value"].shape == (2, WINDOW, 8 * 128)
    rows = [got[:, :n].astype(jnp.float32)]
    for at in range(n, n + steps):
        out, mut = cached.apply({"params": own, "cache": cache},
                                x[:, at:at + 1], mutable=["cache"])
        cache = mut["cache"]
        rows.append(out.astype(jnp.float32))
    np.testing.assert_allclose(jnp.concatenate(rows, 1), want, atol=0.03)
    assert len(calls) == steps
    assert all(kw == {"block": WINDOW, "interpret": True,
                      "name": "hvd.window_attend"} for _, kw in calls)
    assert [int(lens[0]) for lens, _ in calls] \
        == [min(at + 1, WINDOW) for at in range(n, n + steps)]


# ---------------------------------------------------------------- the router
def test_the_correction_bias_enters_the_choice_and_not_the_weights():
    scores = jnp.asarray([[0.50, 0.49, 0.30, 0.10],
                          [0.20, 0.60, 0.59, 0.58]])
    bias = jnp.asarray([0.0, -0.2, 0.0, 0.25])
    weights, local, here = moe.route(scores, 2, (0, 4), bias=bias)
    # Token 0: 0.50 and 0.10 + 0.25 (0.49 - 0.2 is out); token 1: 0.58 +
    # 0.25 and 0.59 (0.60 - 0.2 is out).
    assert sorted(np.asarray(local[0])) == [0, 3]
    assert sorted(np.asarray(local[1])) == [2, 3]
    picked = np.take_along_axis(np.asarray(scores), np.asarray(local), -1)
    np.testing.assert_allclose(weights, picked / picked.sum(-1,
                                                            keepdims=True),
                               rtol=1e-6)
    plain, local0, _ = moe.route(scores, 2, (0, 4))
    assert sorted(np.asarray(local0[0])) == [0, 1] and bool(here.all())
    np.testing.assert_allclose(np.asarray(plain).sum(-1), 1.0, rtol=1e-6)


def routed(held, **kw):
    return moe.RoutedExperts(num_experts=16, per_token=2, d_ff=32,
                             held=held, shared=0, bias=True,
                             dtype=jnp.float32, param_dtype=jnp.float32, **kw)


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer(toy):
    """The guide's section 4, with the bias in the choice and no shared
    expert: the toy's 16 experts over 4 chips, 4 held each (the cell's
    256 over 16).  The partial results of all the shares add up to what
    the reference gives the layer with every expert in one place;
    through the program's layer and the reference's."""
    cfg = {**toy, "experts_held": [0, 16], "n_routed_experts": 16}
    whole = kit.seeded_layer(FAMILY, cfg)
    x = jax.random.normal(jax.random.key(3), (2, 11, 64))
    with jax.default_matmul_precision("highest"):
        uncut = ref.feed_forward(
            {"mlp_norm": {"scale": jnp.ones(64)}, "moe": whole["moe"]},
            x, cfg) - x
        normed = x * jax.lax.rsqrt(
            jnp.mean(x * x, -1, keepdims=True) + cfg["layernorm_epsilon"])
        total_program = total_reference = 0.0
        for first in (0, 4, 8, 12):
            held = (first, 4)
            mine = kit.seeded_layer(FAMILY, toy, experts_held=list(held))
            for name in ("experts_gate", "experts_up", "experts_down"):
                np.testing.assert_array_equal(
                    mine["moe"][name], whole["moe"][name][first:first + 4])
            np.testing.assert_array_equal(mine["moe"]["router_bias"],
                                          whole["moe"]["router_bias"])
            total_program = total_program + routed(held).apply(
                {"params": mine["moe"]}, normed)
            total_reference = total_reference + ref.experts_share(
                mine["moe"], normed.reshape(-1, 64), toy, held
            ).reshape(x.shape)
    assert float(jnp.max(jnp.abs(whole["moe"]["router_bias"]))) > 0.01
    np.testing.assert_allclose(total_reference, uncut, atol=5e-6)
    np.testing.assert_allclose(total_program, uncut, atol=5e-6)


# ------------------------------------------------------------------ the model
def test_the_toy_has_five_window_layers_between_two_global_ones(toy,
                                                                params):
    """The rehearsal sizes: hidden 64; 4 query heads over 1 key-value head
    in the two global layers and over 2 in the five window layers of 8
    positions; keys 24, values 16, 8 rotary channels; a dense MLP of 128
    in layer 0, then 16 experts of width 32, top-2, experts 4 to 7
    held; vocabulary 256."""
    assert toy["layer_types"] == ["attention"] + ["window"] * 5 \
        + ["attention"]
    assert "mlp" in params["layer_0"] and "moe" not in params["layer_0"]
    assert "sink" in params["layer_1"]["attn"] \
        and "sink" not in params["layer_6"]["attn"]
    assert params["layer_1"]["attn"]["wk"]["kernel"].shape == (64, 2, 24)
    assert params["layer_6"]["attn"]["wk"]["kernel"].shape == (64, 1, 24)
    assert params["layer_6"]["attn"]["wv"]["kernel"].shape == (64, 1, 16)
    assert "shared_gate" not in params["layer_3"]["moe"]


@pytest.mark.parametrize("length", [1, 3, 8, 9, 23, 41])
def test_the_whole_forward_pass_agrees_with_the_reference(length, toy,
                                                          params):
    model = hybrid.HybridLM(model_config(toy))
    tokens = tokens_of(length, 2, length)
    got = jax.jit(model.apply)({"params": params}, tokens)
    want = kit.reference_logits(ref, params, tokens, toy)
    assert got.shape == want.shape == (2, length, 256)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("n, bucket", [(1, 8), (5, 8), (8, 8), (13, 16),
                                       (29, 32), (50, 64)])
def test_prefill_of_a_padded_bucket_then_decode_through_the_slot_cache(
        n, bucket, toy, params, slot_programs):
    """A prompt of ``n`` (shorter than the window of 8, as long, longer)
    right-padded to ``bucket`` with ``lengths = n``, inserted as row 2
    of a ``DenseSlotCache`` of 3 rows whose last occupant was another
    stream, then 30 tokens decoded there, the rings going round three
    times and more: every row of logits against the reference's full
    forward pass."""
    cache, _, _ = kit.decode_through_the_slot_cache(
        ref, toy, params, slot_programs(), n, bucket, 30)
    assert [kind for kind in sorted(cache._attend_kinds)] \
        == [(2, 128, 0), (5, WINDOW, 0)]


# -------------------------------------------------------------- the counts
def test_the_counts_at_the_published_widths():
    """ISSUE 36's arithmetic, from the configuration's own file."""
    cfg = kit.load(FAMILY)
    counts = mimo_v2_counts
    assert counts.layers(cfg) == (2, 5, 6)
    assert counts.attention_params(cfg, 8) == 94_371_840
    assert counts.attention_params(cfg, 4) == 89_128_960
    weights = counts.dense_params(cfg) + 6 * 16 * 25_165_824 \
        + 19072 * 4096
    assert abs(weights * 2 - 6.86e9) < 0.01e9           # the file on the chip
    full = [4050] * 64
    attend = counts.decode_attend_bytes_per_step(cfg, full)
    assert attend == 2 * (64 * 4051 * 4 * 320 * 2
                          + 64 * 64 * (192 * 2 + 128 * 4))
    assert 1.32e9 < attend < 1.34e9
    assert counts.moe_held_expert_bytes_per_step(cfg, full) \
        == 6 * 16 * 25_165_824 * 2
    assert counts.moe_routed_row_bytes_per_step(cfg, full) \
        == 6 * 64 * 8 * 4096 * 6
    total = counts.decode_bytes_per_step(cfg, full)
    assert 7.4e9 < total < 7.8e9
    # A window layer reads its last 128 positions, a global layer all.
    short, long = (counts.decode_bytes_per_step(cfg, [c] * 64)
                   for c in (100, 1100))
    assert long - short == 64 * (1000 * 2 * 4 * 320 * 2
                                 + 28 * 5 * 8 * 320 * 2)
    import tracing
    reader = harness.load_json(harness.HERE, "layer_metrics",
                               "kernels.decode_attend_roofline.json")
    facts = {"counters": {"decode_attend_bytes_per_step": 819e6},
             "peaks": {"hbm_bytes_per_s": 819e9},
             "metrics": {"kernels.decode_attend_device_ms_per_step": 2.0}}
    assert tracing.evaluate(reader["reader"], facts) == pytest.approx(50.0)
    facts["metrics"] = {}                  # the plain form: no such kernel
    assert tracing.evaluate(reader["reader"], facts) is None
    share = harness.load_json(harness.HERE, "layer_metrics",
                              "replica.window_cache_share.json")
    facts["counters"] = {"stats.window_bytes": 5, "stats.cache_bytes": 100}
    assert tracing.evaluate(share["reader"], facts) == pytest.approx(5.0)
    del facts["counters"]["stats.window_bytes"]          # the parent
    assert tracing.evaluate(share["reader"], facts) is None
    # The program's own count of one generated token is this chip's.
    from horovod_tpu.telemetry import perfmodel
    config = hybrid.HybridConfig(**harness.build_args(cfg))
    assert abs(64 * perfmodel.hybrid_decode_flops(config, 4050)
               / counts.decode_flops_per_step(cfg, full) - 1.0) < 1e-6
    assert perfmodel.hybrid_decode_flops(config, 50) \
        < perfmodel.hybrid_decode_flops(config, 128) \
        < perfmodel.hybrid_decode_flops(config, 129)
    assert perfmodel.hybrid_decode_flops(config, 1129) \
        - perfmodel.hybrid_decode_flops(config, 129) \
        == 2.0 * 64 * 320 * 2 * 1000


# ------------------------------------------------------------- the replica
def test_the_replica_serves_the_references_best_and_counts_by_layer_kind(
        toy, params, solo_world):
    """Seven requests over three slots on the normal path, prompts
    shorter and longer than the window, streams that leave it far
    behind: every served token is the reference's best (float32); the
    rings are 5 of the cache's 7 layers and counted as ``window_bytes``;
    the attend counters take ``min(length, 8)`` in a window layer."""
    rng = random.Random(36)
    prompts = [[rng.randrange(2, 256) for _ in range(n)]
               for n in (1, 3, 8, 9, 17, 26, 30)]
    new = [12, 30, 7, 25, 5, 21, 9]
    ex = kit.executor(FAMILY, model_config(toy), params)
    try:
        assert ex.family is hybrid.ROUTED_FAMILY
        stats = ex.stats
        assert stats["state_bytes"] == 0
        assert stats["cache_bytes"] == stats["cache_aliased_bytes"]
        assert "kv_bytes" not in stats     # cache_bytes less state_bytes
        ring = 3 * WINDOW * 2 * (24 + 16) * 4
        whole = 3 * 64 * 1 * (24 + 16) * 4
        assert stats["window_bytes"] == 5 * ring
        assert stats["cache_bytes"] == 5 * ring + 2 * whole + 7 * 3 * 4
        streams = kit.serve(ex, prompts, new)
    finally:
        ex.close()
    assert [len(s) for s in streams] == new
    for prompt, served in zip(prompts, streams):
        assert kit.widest_gap(ref, params, toy, prompt, served) <= 1e-5
    # A layer's worth: (2 global x length + 5 window x min(length, 8)) / 7,
    # and the plain form reads 64 positions there and 8 here.
    assert 0 < stats["attend_live_positions"] < stats["attend_read_positions"]
    dispatched = stats["attend_read_positions"] / ((2 * 64 + 5 * 8) / 7)
    assert dispatched == pytest.approx(round(dispatched), abs=0.2)
    assert stats["moe_expert_slots"] % (6 * 4) == 0


# --------------------------------------------- the benchmark's own comparison
@pytest.mark.parametrize("seed", [3, 2147483659])
def test_the_cells_control_in_int8_comes_out_not_correct(seed, monkeypatch,
                                                         capsys):
    """``--check control`` of the new cell at its rehearsal sizes (a
    ``ReplicaExecutor`` rehearsal whose ``correct`` is true): the served
    tokens, their replay, the attention over what the program fed its
    own and the router's rule stay inside the toy limits, and the
    reference computed in int8 does not, by the logits' limits (what the
    program fed its own is not the control's to round)."""
    seen = kit.check_control(monkeypatch, capsys, FAMILY, seed)
    assert seen["code"] == 0 and seen["ok"] and not seen["problems"]
    assert seen["served_tokens"] > 200
    assert all(seen[key] <= limit for key, limit in seen["limits"].items())
    over = {key for key, limit in seen["limits"].items()
            if seen["control_" + key] > limit}
    assert "replay_err" in over and len(over) >= 2, seen
    assert not over & {"attend_gap", "route_gap"}


def without_an_expert(monkeypatch):
    route = moe.route

    def without_the_first(scores, per_token, held, **kw):
        weights, local, here = route(scores, per_token, held, **kw)
        return weights, local, here & (local != 0)
    monkeypatch.setattr(moe, "route", without_the_first)


def bias_in_the_weights(monkeypatch):
    route = moe.route

    def biased(scores, per_token, held, *, bias=None, **kw):
        return route(scores + bias, per_token, held, **kw)
    monkeypatch.setattr(moe, "route", biased)


def ring_keeps_the_padding(monkeypatch):
    cached = kvcache.cached_attention
    monkeypatch.setattr(
        hybrid, "cached_attention", lambda *a, lengths=None, **kw:
        cached(*a, **kw, lengths=None if kw.get("window") else lengths))


def configured(**over):
    """A fault that is a wrong argument of the model's configuration."""
    def plant(monkeypatch):
        build = harness.build_args
        monkeypatch.setattr(harness, "build_args",
                            lambda config: {**build(config), **over})
    return plant


def faults(window: int, head_dim: int) -> dict:
    """name -> (how it is planted, the numbers it must push over their
    limits), for a model of this window and head width: the tests plant
    them at the toy size, a chip script at the cell's."""
    return {
        "window_one_longer": (configured(window=window + 1), {"attend_gap"}),
        "window_one_shorter": (configured(window=window - 1),
                               {"attend_gap"}),
        "sink_left_out": (configured(window_sink=False), {"attend_gap"}),
        "value_scale_left_out": (configured(attn_value_scale=1.0),
                                 {"attend_gap"}),
        "rotary_bases_swapped": (configured(rope_theta=10000.0,
                                            window_rope_theta=10000000.0),
                                 {"attend_gap"}),
        "rotary_on_every_channel": (configured(attn_rotary_dim=head_dim),
                                    {"attend_gap"}),
        "bias_in_the_weights": (bias_in_the_weights, {"route_gap"}),
        "an_expert_left_out": (without_an_expert, set()),
        "ring_keeps_the_padding": (ring_keeps_the_padding, {"attend_gap"}),
    }


FAULTS = faults(WINDOW, 24)
ALONE = ("replay_err", "attend_gap", "route_gap")


def test_the_sound_program_reads_rounding_alone(toy, params):
    seen = kit.program_against_reference(ref, toy, params, ALONE)
    limits = toy["served_check"]["limits"]
    assert all(seen[key] <= limits[key] / 10 for key in ALONE), seen


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_comes_out_over_a_toy_limit(fault, toy, params,
                                                    monkeypatch):
    """The program broken underneath, nine ways, each over at least one
    of the cell's toy limits and over the one that is there to catch it
    (``attend_gap`` holds the attention's arithmetic apart from the
    logits, ``route_gap`` the router's rule; with random weights the
    logits alone hardly see an attention layer)."""
    plant, must = FAULTS[fault]
    plant(monkeypatch)
    seen = kit.program_against_reference(ref, toy, params, ALONE)
    limits = toy["served_check"]["limits"]
    over = {key for key in ALONE if seen[key] > limits[key]}
    assert over and must <= over, (seen, limits)
    assert "replay_err" in over, seen         # float32: the logits see it too
