"""A.X-K1 on the hybrid decoder: multi-head latent attention (a 576-wide
latent row a position, YaRN, the absorbed decode kernel ``hvd.mla_decode``)
and group-limited routing over experts with a shared one; the model
through the slot cache and the replica, all against the benchmark's
plain float32 reference (benchmarks/chip/axk1_reference.py, the
published, non-absorbed form) on its seeded weights, comparing logits.
Toy widths: the rehearsal sizes of the configuration's own file (hidden
64, 4 heads, ranks 32, heads of 16 + 8 channels and values of 16, 16
experts in 4 groups)."""
from __future__ import annotations

import copy
import dataclasses
import math
import random
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

import servekit as kit
from horovod_tpu.models import hybrid, moe, transformer
from horovod_tpu.ops import mla
from horovod_tpu.serving import slotcache
from servekit import harness, model_config, solo_world, tokens_of  # noqa: F401
from test_decode_attention import pallas_calls

FAMILY = "axk1"
ref, axk1_counts = kit.module(FAMILY), kit.module(FAMILY, "counts")
toy, params = kit.fixtures(FAMILY)
slot_programs = kit.slot_programs_fixture()


def interpreted(monkeypatch):
    """``hvd.mla_decode`` as the chip takes it, interpreted here: the
    kernel where the rule finds a block, and the slot cache counting it."""
    real = mla._mla_pallas
    monkeypatch.setattr(mla, "_on_tpu", lambda: True)
    monkeypatch.setattr(mla, "_mla_pallas", lambda *a, **kw: real(
        *a, **{**kw, "interpret": True}))


# ------------------------------------------------------- YaRN and the scale
def test_yarn_frequencies_and_the_score_scale_at_the_published_widths():
    """low 10 and high 23 of the 32 rotary pairs (base 10,000, factor 32
    over 4,096 positions, beta 32 and 1), and tau = 192^-1/2 x m^2 =
    0.130861, in the program and in the reference alike."""
    cfg = kit.load(FAMILY)
    yarn = cfg["rope_scaling"]
    plain = 10000.0 ** -(np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / (23 - 10), 0, 1)
    want = plain * (1 - ramp) + plain / 32 * ramp
    got = hybrid.yarn_frequencies(64, 10000.0, tuple(yarn.items()))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(ref.inverse_frequencies(cfg), want, rtol=1e-12)
    assert np.array_equal(got[:11], plain[:11].astype(np.float32))
    np.testing.assert_allclose(got[23:], plain[23:] / 32, rtol=1e-6)
    m = 1 + 0.1 * math.log(32)
    assert m == pytest.approx(1.34657, abs=1e-5)
    config = model_config(cfg)
    assert config.head_dim == 192
    assert hybrid.latent_scale(config) == pytest.approx(0.130861, abs=1e-6)
    assert ref.softmax_scale(cfg) == pytest.approx(0.130861, abs=1e-6)
    assert hybrid.latent_scale(dataclasses.replace(
        config, rope_scaling=None)) == pytest.approx(192 ** -0.5)
    # mscale over mscale_all_dim: 1 here, so cos and sin are as they are.
    x = jax.random.normal(jax.random.key(0), (1, 5, 2, 64))
    at = jnp.arange(5)[None]
    np.testing.assert_allclose(
        hybrid._rotate(x, at, width=64, theta=10000.0,
                       scaling=tuple(yarn.items())),
        transformer.apply_rope(x, at, 10000.0, jnp.asarray(want, jnp.float32)),
        atol=1e-6)


# ---------------------------------------------------- the two forms, by hand
def latent_operands(seed: int, b: int, s: int, h: int, rank: int, rope: int,
                    nope: int = 16, wide: int = 16):
    keys = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(keys[0], (b, 1, h, nope)),
            jax.random.normal(keys[1], (b, 1, h, rope)),
            jax.random.normal(keys[2], (b, s, rank)),
            jax.random.normal(keys[3], (b, s, rope)),
            jax.random.normal(keys[4], (rank, h, nope)) * rank ** -0.5,
            jax.random.normal(keys[5], (rank, h, wide)) * rank ** -0.5)


@pytest.mark.parametrize("rank, rope", [(32, 8), (512, 64)])
def test_the_absorbed_form_is_the_expanded_softmax(rank, rope):
    """``q~ = W_UK^T q_nope`` over the latent rows, then ``W_UV`` on the
    weighted latent, against each head's expanded keys and values in a
    full masked softmax: the same function."""
    from horovod_tpu.ops import decode_attention as da
    q_nope, q_pe, c_kv, k_pe, w_uk, w_uv = latent_operands(3, 3, 24, 4,
                                                           rank, rope)
    lens = jnp.asarray([1, 13, 24])
    rows = mla.latent_row(c_kv, k_pe, jnp.float32)
    assert rows.shape == (3, 24, mla.row_width(rank, rope))
    assert rows.shape[-1] % 128 == 0
    with jax.default_matmul_precision("highest"):
        got = mla.emit(mla.mla_plain(mla.absorb(q_nope, q_pe, w_uk), rows,
                                     lens[:, None] - 1, 0.13, rank),
                       w_uv, jnp.float32)
        q, k, v = mla.expand(q_nope, q_pe, c_kv, k_pe, w_uk, w_uv)
        want = da.attend_plain(q, k, v, lens[:, None] - 1, 0.13)
    assert got.shape == want.shape == (3, 1, 4, 16)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


LENGTHS = {"ragged": (1, 7, 16, 17, 33, 64), "one": (1,) * 6,
           "full": (64,) * 6}


@pytest.mark.parametrize("lengths", sorted(LENGTHS))
@pytest.mark.parametrize("rank, rope, dtype", [(32, 8, jnp.float32),
                                               (32, 8, jnp.bfloat16),
                                               (512, 64, jnp.bfloat16)])
def test_the_kernel_interpreted_agrees_with_the_plain_form_and_writes_the_row(
        rank, rope, dtype, lengths):
    """``hvd.mla_decode`` at the toy widths and at A.X-K1's (576 -> 640
    lanes), 16 query heads, blocks of 16: ragged lengths, a slot of one
    position, full slots; the positions past a slot's length hold NaN
    and must not reach the result; the step's row, handed to the kernel,
    lies in the returned leaf where ``write_rows`` puts it."""
    heads, s, block = 16, 64, 16
    lens = np.asarray(LENGTHS[lengths], np.int32)
    b = len(lens)
    keys = jax.random.split(jax.random.key(5), 3)
    width = mla.row_width(rank, rope)
    q = jax.random.normal(keys[0], (b, 1, heads, width)).astype(dtype)
    leaf = jax.random.normal(keys[1], (b, s, width)).astype(dtype)
    new = jax.random.normal(keys[2], (b, 1, width)).astype(dtype)
    at = jnp.asarray(lens - 1)
    dead = np.arange(s)[None, :, None] >= lens[:, None, None] - 1
    leaf = jnp.where(dead, jnp.nan, leaf).astype(dtype)
    got, written = mla._mla_pallas(q, leaf, new, jnp.asarray(lens), at, 0.07,
                                   rank, block=block, interpret=True)
    whole = mla.write_rows(leaf, new, at)
    want = mla.mla_plain(q, jnp.where(jnp.isnan(whole), 0, whole),
                         jnp.asarray(lens)[:, None] - 1, 0.07, rank)
    assert got.shape == (b, 1, heads, rank) and got.dtype == jnp.float32
    tol = 5e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
    np.testing.assert_array_equal(np.asarray(written, np.float32),
                                  np.asarray(whole, np.float32))


def test_the_entry_point_takes_the_kernel_by_the_rule_and_the_name():
    """``kernel_block``: 1,024 positions of A.X-K1's leaf on the chip (or
    interpreted), none elsewhere; the plain form writes the row where
    the kernel does; the kernel carries the name a device trace selects
    it by."""
    assert mla.row_width(512, 64) == 640
    assert mla.kernel_block((64, 14336, 640), jnp.bfloat16, True) == 1024
    assert mla.kernel_block((64, 14336, 640), jnp.bfloat16) == 0  # a CPU
    q_nope, q_pe, c_kv, k_pe, w_uk, _ = latent_operands(1, 2, 32, 4, 32, 8)
    leaf = mla.latent_row(c_kv, k_pe, jnp.float32)
    q = mla.absorb(q_nope, q_pe, w_uk)
    lens, at = jnp.asarray([5, 32]), jnp.asarray([4, 31])
    new = leaf[:, :1] + 1.0
    plain, plainly = mla.mla_decode(q, leaf, new, lens, at, 0.2, 32)
    kernel, written = mla.mla_decode(q, leaf, new, lens, at, 0.2, 32,
                                     interpret=True)
    np.testing.assert_allclose(kernel, plain, atol=3e-6)
    np.testing.assert_array_equal(written, plainly)
    assert float(written[0, 4, 0]) == float(leaf[0, 0, 0] + 1.0)
    jaxpr = jax.make_jaxpr(lambda *a: mla._mla_pallas(
        *a, 0.2, 32, block=16, interpret=True))(q, leaf, new, lens, at)
    call, = pallas_calls(jaxpr.jaxpr)
    assert "hvd.mla_decode" in str(call.params["name"]) \
        or "hvd.mla_decode" in str(call.params.get("name_and_src_info"))


# ---------------------------------------------------------------- the router
@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("biased", [False, True])
def test_group_limited_routing_is_the_brute_force_choice(seed, biased):
    """192 experts in 8 groups of 24, the 4 best groups by the sum of
    their two largest (biased) scores, the 8 largest among their 96:
    against a loop over the tokens in numpy."""
    scores = jax.random.uniform(jax.random.key(seed), (32, 192))
    bias = jax.random.uniform(jax.random.key(seed + 99), (192,), jnp.float32,
                              -0.05, 0.05) if biased else None
    weights, local, here = moe.route(scores, 8, (0, 192), scaling=2.5,
                                     bias=bias, groups=(8, 4))
    s = np.asarray(scores, np.float64)
    choice = s + (np.asarray(bias) if biased else 0.0)
    for n in range(32):
        groups = choice[n].reshape(8, 24)
        score = np.sort(groups, -1)[:, -2:].sum(-1)
        kept = np.argsort(-score)[:4]
        pool = np.concatenate([g * 24 + np.arange(24) for g in kept])
        want = set(pool[np.argsort(-choice[n, pool])[:8]].tolist())
        assert set(np.asarray(local[n]).tolist()) == want
        top = s[n, sorted(want)]
        np.testing.assert_allclose(
            sorted(np.asarray(weights[n])), sorted(top / top.sum() * 2.5),
            rtol=1e-5)
    assert bool(here.all())
    flat, _, _ = moe.route(scores, 8, (0, 192))
    grouped, _, _ = moe.route(scores, 8, (0, 192), groups=(1, 1))
    np.testing.assert_array_equal(flat, grouped)      # one group: no limit
    # The reference's rule is the program's.
    cfg = {"n_group": 8, "topk_group": 4, "num_experts_per_tok": 8,
           "norm_topk_prob": True, "routed_scaling_factor": 2.5}
    if not biased:
        _, chosen, _, _ = ref.routing(scores, cfg)
        assert [set(r) for r in np.asarray(chosen).tolist()] \
            == [set(r) for r in np.asarray(local).tolist()]


def test_the_shares_of_all_16_chips_add_up_to_the_uncut_layer(toy):
    """The guide's section 4 for a whole expert layer: the toy's 16
    experts over 16 chips, one each (the cell's 192 over 16); attention
    and the shared expert, which every chip computes alike, counted
    once; the routed parts of all the shares, through the program's
    layer and the reference's, add up to what the reference gives the
    layer with every expert in one place."""
    cfg = {**toy, "experts_held": [0, 16], "n_routed_experts": 16}
    whole = kit.seeded_layer(FAMILY, cfg)
    x = jax.random.normal(jax.random.key(3), (2, 11, 64))
    shared_w = {name: whole["moe"][name] for name in
                ("shared_gate", "shared_up", "shared_down")}
    with jax.default_matmul_precision("highest"):
        mixed = ref.attention(whole, x, cfg)
        uncut = ref.feed_forward(whole, mixed, cfg)
        normed = ref.reference.rms_norm(mixed, 1.0, cfg["rms_norm_eps"])
        flat = normed.reshape(-1, 64)
        once = mixed + ref.gated_mlp(flat, *(
            shared_w[n]["kernel"] for n in ("shared_gate", "shared_up",
                                            "shared_down"))).reshape(x.shape)
        program = reference = once
        for first in range(16):
            held = (first, 1)
            mine = kit.seeded_layer(FAMILY, toy, experts_held=list(held))
            np.testing.assert_array_equal(
                mine["moe"]["experts_gate"],
                whole["moe"]["experts_gate"][first:first + 1])
            routed_only = {k: v for k, v in mine["moe"].items()
                           if not k.startswith("shared")}
            program = program + moe.RoutedExperts(
                num_experts=16, per_token=2, d_ff=32, held=held, shared=0,
                scaling=cfg["routed_scaling_factor"], groups=(4, 2),
                dtype=jnp.float32, param_dtype=jnp.float32).apply(
                    {"params": routed_only}, normed)
            reference = reference + ref.experts_share(
                mine["moe"], flat, toy, held).reshape(x.shape)
    np.testing.assert_allclose(reference, uncut, atol=5e-6)
    np.testing.assert_allclose(program, uncut, atol=5e-6)


# ------------------------------------------------------------------ the model
def test_the_toy_has_latent_attention_and_a_shared_expert(params):
    attn = params["layer_1"]["attn"]
    assert attn["wq_b"]["kernel"].shape == (32, 4, 24)
    assert attn["wkv_a"]["kernel"].shape == (64, 40)
    assert attn["wkv_b"].shape == (32, 4, 32)
    assert "mlp" in params["layer_0"] and "moe" not in params["layer_0"]
    assert "shared_gate" in params["layer_1"]["moe"]
    assert float(jnp.min(attn["kv_norm"]["scale"])) < 0.9


@pytest.mark.parametrize("length", [1, 3, 9, 23, 41])
def test_the_whole_forward_pass_agrees_with_the_reference(length, toy,
                                                          params):
    model = hybrid.HybridLM(model_config(toy))
    tokens = tokens_of(length, 2, length)
    got = jax.jit(model.apply)({"params": params}, tokens)
    want = kit.reference_logits(ref, params, tokens, toy)
    assert got.shape == want.shape == (2, length, 256)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("n, bucket", [(1, 8), (8, 8), (13, 16), (50, 64)])
def test_prefill_of_a_padded_bucket_then_decode_through_the_slot_cache(
        n, bucket, kernel, toy, params, slot_programs, monkeypatch):
    """A prompt of ``n`` right-padded to ``bucket`` (the expanded form,
    in blocks), inserted as row 2 of a ``DenseSlotCache`` of 3 rows whose
    last occupant was another stream, then 20 tokens decoded there in
    the absorbed form (the plain form, or the kernel interpreted, which
    writes each step's row): every row of logits against the reference's
    full forward pass."""
    if kernel:
        interpreted(monkeypatch)
    cache, model, tokens = kit.decode_through_the_slot_cache(
        ref, toy, params, slot_programs("kernel" if kernel else ""), n,
        bucket, 20)
    assert cache.tree["layer_0"]["attn"]["latent"].shape == (3, 128, 128)
    assert cache._attend_kinds == [(5, 128, 128 if kernel else 0)]
    assert cache.stats["attend_layers"] == 5
    assert cache.stats["attend_write_fused_layers"] == (5 if kernel else 0)
    with pytest.raises(ValueError, match="whole prompt or one token"):
        model.apply({"params": params, "cache": cache.tree},
                    tokens[:, :2].repeat(3, 0), mutable=["cache"])


# -------------------------------------------------------------- the counts
def test_the_counts_at_the_published_widths():
    """ISSUE 40's arithmetic, from the configuration's own file."""
    cfg = kit.load(FAMILY)
    counts = axk1_counts
    assert counts.layers(cfg) == (5, 4)
    assert counts.row_width(cfg) == 576
    assert abs(counts.attention_params(cfg) - 101.1e6) < 0.05e6
    expert = 3 * 7168 * 2048
    assert abs(expert - 44.04e6) < 0.01e6
    weights = (counts.dense_params(cfg) + 4 * 12 * expert + 20480 * 7168) \
        + 5 * 2 * 7168 + 5 * (1536 + 512) + 7168
    assert abs(weights * 2 - 6.98e9) < 0.01e9           # the file on the chip
    full = [6400] * 64
    attend = counts.mla_decode_bytes_per_step(cfg, full)
    assert attend == 5 * (64 * 6401 * 576 * 2 + 64 * 64 * (576 * 2 + 512 * 4))
    assert 2.40e9 < attend < 2.45e9
    # 139,264 operations for a position's 1,152 bytes: 121 a byte.
    per_position = 2 * 64 * (576 + 512)
    assert per_position == 139_264 and round(per_position / 1152) == 121
    assert counts.moe_held_expert_bytes_per_step(cfg, full) \
        == 4 * 12 * expert * 2
    assert counts.moe_routed_row_bytes_per_step(cfg, full) \
        == 4 * 64 * 8 * 7168 * 6
    short, long = (counts.decode_bytes_per_step(cfg, [c] * 64)
                   for c in (100, 1100))
    assert long - short == 5 * 64 * 1000 * 576 * 2
    import tracing
    for name, facts, want in (
            ("kernels.mla_decode_roofline",
             {"counters": {"mla_decode_bytes_per_step": 819e6},
              "peaks": {"hbm_bytes_per_s": 819e9},
              "metrics": {"kernels.mla_decode_device_ms_per_step": 2.0}},
             50.0),
            ("kernels.mla_decode_roofline",
             {"counters": {}, "peaks": {}, "metrics": {}}, None)):
        reader = harness.load_json(harness.HERE, "layer_metrics",
                                   name + ".json")
        got = tracing.evaluate(reader["reader"], facts)
        assert got == (pytest.approx(want) if want else None)
    # The kernel's device time: the median call times the five a step.
    ops = [tracing.Span(f"hvd.mla_decode.{i}", 0.01 * i,
                        0.01 * i + 0.0007, "custom-call")
           for i in range(1, 6)]
    timeline = tracing.Timeline(
        [tracing.Span("bench.serve.step", 0.0, 1.0)],
        {0: {"XLA Ops": ops}})
    reader = harness.load_json(harness.HERE, "layer_metrics",
                               "kernels.mla_decode_device_ms_per_step.json")
    got = tracing.evaluate(reader["reader"], {
        "timeline": timeline, "labels": {"bench.serve.step": ["decode"]}})
    assert got == pytest.approx(3.5)
    # The program's own count of one generated token is this chip's.
    from horovod_tpu.telemetry import perfmodel
    config = hybrid.HybridConfig(**harness.build_args(cfg))
    assert abs(64 * perfmodel.hybrid_decode_flops(config, 6400)
               / counts.decode_flops_per_step(cfg, full) - 1.0) < 1e-6
    assert perfmodel.hybrid_decode_flops(config, 1100) \
        - perfmodel.hybrid_decode_flops(config, 100) \
        == 5 * 139_264 * 1000.0


def test_a_warm_up_bucket_below_the_power_of_two_takes_the_prompt():
    """``prompt_bucket``: the next power of two, or a listed warm-up
    bucket between the prompt and it; lists of powers of two (every
    other cell's) bucket as before."""
    cfg = types.SimpleNamespace(max_seq=14336,
                                warmup_buckets=(3072, 4096, 6144, 10240))
    assert [slotcache.prompt_bucket(cfg, n)
            for n in (5, 2048, 2049, 3073, 4097, 6145, 8193, 12000)] \
        == [8, 2048, 3072, 4096, 6144, 8192, 10240, 14336]
    for warm in ((64, 128, 256, 512, 1024), (1024, 2048, 4096, 8192), ()):
        plain = types.SimpleNamespace(max_seq=12288, warmup_buckets=warm)
        assert all(slotcache.prompt_bucket(plain, n)
                   == min(max(8, 1 << (n - 1).bit_length()), 12288)
                   for n in range(1, 9000, 7))


# ------------------------------------------------------------- the replica
def test_the_replica_serves_the_references_best_and_counts_latent_layers(
        toy, params, solo_world):
    """Six requests over three slots on the normal path: every served
    token is the reference's best (float32); the five latent leaves are
    the cache and counted as attention layers."""
    rng = random.Random(40)
    prompts = [[rng.randrange(2, 256) for _ in range(n)]
               for n in (1, 3, 9, 17, 26, 30)]
    new = [12, 30, 7, 25, 5, 21]
    ex = kit.executor(FAMILY, model_config(toy), params)
    try:
        assert ex.family is hybrid.ROUTED_FAMILY
        stats = ex.stats
        assert stats["cache_bytes"] == stats["cache_aliased_bytes"] \
            == 5 * 3 * 64 * 128 * 4 + 5 * 3 * 4
        assert stats["state_bytes"] == stats["window_bytes"] == 0
        assert stats["attend_layers"] == 5
        streams = kit.serve(ex, prompts, new)
    finally:
        ex.close()
    assert [len(s) for s in streams] == new
    for prompt, served in zip(prompts, streams):
        assert kit.widest_gap(ref, params, toy, prompt, served) <= 1e-5
    assert 0 < stats["attend_live_positions"] < stats["attend_read_positions"]
    assert stats["moe_expert_slots"] % (4 * 4) == 0


def test_a_long_prefill_runs_the_expert_products_in_chunks(monkeypatch):
    """A call whose pairs' float32 rows pass ``WHOLE_BYTES`` runs the
    products over chunks of tokens, one after the other, the last
    padded: the same result and counters as all at once; every other
    call (a decode step, MiMo's 8,192 prompt at 2^30 exactly) all at
    once."""
    assert moe.chunk_tokens(64, 8, 7168) == 0
    assert moe.chunk_tokens(8192, 8, 4096) == 0                  # MiMo
    assert moe.chunk_tokens(4096, 8, 7168) == 0
    assert moe.chunk_tokens(6144, 8, 7168) == 2048               # A.X-K1
    assert moe.chunk_tokens(10240, 8, 7168) == 2048
    layer = moe.RoutedExperts(num_experts=16, per_token=2, d_ff=32,
                              held=(0, 8), groups=(4, 2), scaling=2.5,
                              dtype=jnp.float32, param_dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(0), (1, 37, 64))
    own = layer.init(jax.random.key(1), x)
    whole, seen = layer.apply(own, x, mutable=["counters"])
    monkeypatch.setattr(moe, "WHOLE_BYTES", 16)
    monkeypatch.setattr(moe, "CHUNK_BYTES", 8 * 2 * 64 * 4)
    assert moe.chunk_tokens(37, 2, 64) == 8
    parts, counted = layer.apply(own, x, mutable=["counters"])
    np.testing.assert_allclose(parts, whole, atol=1e-5)
    assert jax.tree_util.tree_map(int, counted) \
        == jax.tree_util.tree_map(int, seen)


# --------------------------------------------- the benchmark's own comparison
@pytest.mark.parametrize("seed", [3, 2147483659])
def test_the_cells_control_in_int8_comes_out_not_correct(seed, monkeypatch,
                                                         capsys):
    """``--check control`` of the new cell at its rehearsal sizes: the
    served tokens, their replay, the attention over what the program fed
    its own and the router's rule stay inside the toy limits, and the
    reference computed in int8 does not, by the logits' limits."""
    seen = kit.check_control(monkeypatch, capsys, FAMILY, seed)
    assert seen["code"] == 0 and seen["ok"] and not seen["problems"]
    assert seen["served_tokens"] > 150
    assert all(seen[key] <= limit for key, limit in seen["limits"].items())
    over = {key for key, limit in seen["limits"].items()
            if seen["control_" + key] > limit}
    assert "replay_err" in over and len(over) >= 2, seen
    assert not over & {"attend_gap", "route_gap"}


class _NoLatentNorm(transformer.RMSNorm):
    """RMSNorm but for the latent's own, which passes its input on."""

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           self.param_dtype)
        if self.name == "kv_norm":
            return x.astype(self.dtype)
        x32 = x.astype(jnp.float32)
        return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True)
                                    + self.eps) * scale).astype(self.dtype)


def plain_rotary(monkeypatch):
    monkeypatch.setattr(hybrid, "yarn_frequencies", lambda width, theta, _:
                        theta ** -(np.arange(0, width, 2) / width))


def no_m_squared(monkeypatch):
    monkeypatch.setattr(hybrid, "latent_scale",
                        lambda cfg: cfg.head_dim ** -0.5)


def groups_ignored(monkeypatch):
    route = moe.route
    monkeypatch.setattr(moe, "route", lambda *a, groups=(1, 1), **kw:
                        route(*a, **kw))


def no_routed_scaling(monkeypatch):
    build = harness.build_args
    monkeypatch.setattr(harness, "build_args",
                        lambda config: {**build(config), "routed_scaling": 1.0})


def no_latent_norm(monkeypatch):
    monkeypatch.setattr(hybrid, "RMSNorm", _NoLatentNorm)


def row_not_written(monkeypatch):
    real = mla.mla_decode
    monkeypatch.setattr(mla, "mla_decode", lambda q, latent, *a, **kw:
                        (real(q, latent, *a, **kw)[0], latent))


# name -> (how it is planted, the numbers it must push over their limits):
# the tests plant them at the toy size, a chip script at the cell's.
FAULTS = {
    "plain_rotary": (plain_rotary, {"attend_gap"}),
    "m_squared_left_out": (no_m_squared, {"attend_gap"}),
    "groups_ignored": (groups_ignored, {"route_gap"}),
    "routed_scaling_left_out": (no_routed_scaling, {"route_gap"}),
    "latent_norm_left_out": (no_latent_norm, {"attend_gap"}),
    "row_not_written": (row_not_written, {"attend_gap"}),
}
ALONE = ("replay_err", "attend_gap", "route_gap")


def test_the_sound_program_reads_rounding_alone(toy, params):
    seen = kit.program_against_reference(ref, toy, params, ALONE)
    limits = toy["served_check"]["limits"]
    assert all(seen[key] <= limits[key] / 10 for key in ALONE), seen


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_comes_out_over_a_toy_limit(fault, toy, params,
                                                    monkeypatch):
    """The program broken underneath, six ways (ISSUE 40's list), each
    over the one of the cell's toy limits that is there to catch it
    (``attend_gap`` holds the latent attention's arithmetic apart from
    the logits, ``route_gap`` the router's rule)."""
    plant, must = FAULTS[fault]
    plant(monkeypatch)
    seen = kit.program_against_reference(ref, toy, params, ALONE)
    limits = toy["served_check"]["limits"]
    over = {key for key in ALONE if seen[key] > limits[key]}
    assert over and must <= over, (seen, limits)


def test_the_reference_follows_the_programs_groups_in_bfloat16(toy):
    """In bfloat16 a token's fourth and fifth groups tie often enough that
    the program and the reference keep different groups; the reference
    follows the program's groups (those its own sown scores keep) where
    its scores tie within ``tie``, as it follows its experts, so the
    logits agree to bfloat16's rounding (following the experts alone,
    this stream read ``replay_err`` 0.545)."""
    cfg = copy.deepcopy(toy)
    cfg["model"]["args"]["dtype"] = "@jax.numpy:bfloat16"
    params = kit.seeded(FAMILY, cfg, seed=2)
    tokens = np.zeros((1, 256), np.int32)
    tokens[0, :120] = np.asarray(jax.random.randint(jax.random.key(2),
                                                    (120,), 2, 256))
    seen = ref.served_gap(cfg)(params, tokens, np.int32(40), np.int32(120))
    assert float(seen["route_flips_sum"]) > 0
    assert float(seen["replay_err"]) < 0.1
