"""Ouro on the hybrid decoder: one stack of layers run several times a
token with the same weights, norms on both sides of every sub-layer, the
final norm after every pass, and a key-value cache for every pass; the
model through the slot cache and the replica, all against the
benchmark's plain float32 reference (benchmarks/chip/ouro_reference.py)
on its seeded weights, comparing logits.  Toy widths: the rehearsal
sizes of the configuration's own file (hidden 64, 4 heads of 16, an MLP
of 128, 2 layers), 3 passes unless a test says otherwise."""
from __future__ import annotations

import dataclasses
import functools
import random
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

import servekit as kit
from horovod_tpu.models import hybrid, kvcache
from horovod_tpu.models.transformer import MLP, RMSNorm
from horovod_tpu.ops import decode_attention as da
from servekit import harness, model_config, solo_world, tokens_of  # noqa: F401

FAMILY = "ouro"
ref, ouro_counts = kit.module(FAMILY), kit.module(FAMILY, "counts")
toy, params = kit.fixtures(FAMILY)
slot_programs = kit.slot_programs_fixture()
PASSES, LAYERS = 3, 2


# ------------------------------------------------------------------ the model
def test_the_configuration_maps_the_published_keys(toy):
    cfg = kit.load(FAMILY)
    config = model_config(cfg)
    assert (config.loops, config.sandwich_norm) == (4, True)
    assert config.layer_types == ("attention",) * 48
    assert (config.num_heads, config.num_kv_heads, config.head_dim,
            config.attn_rotary_dim) == (16, 16, 128, 128)
    assert config.attention_multiplier == 128 ** -0.5
    assert (config.rope_theta, config.rms_norm_eps) == (1e6, 1e-6)
    assert not config.tie_embeddings and cfg["reduced"] == []
    assert model_config(toy).attention_multiplier == 16 ** -0.5


def test_a_looped_stack_takes_attention_layers_alone():
    """A pass keeps a cache of its own in the attention layers' leaves;
    a recurrent state or a latent leaf would be shared by the passes."""
    hybrid.HybridConfig(layer_types=("attention", "window"), window=4,
                        loops=2)
    for kinds in (("mamba", "attention"), ("latent",)):
        with pytest.raises(ValueError, match="loops=2"):
            hybrid.HybridConfig(layer_types=kinds, loops=2)
    with pytest.raises(ValueError, match="loops=0"):
        hybrid.HybridConfig(loops=0)


def test_the_toy_has_one_set_of_weights_a_layer_and_four_norms(params):
    """One set of weights a layer, whatever the passes; four norms a
    layer, each scale away from ones."""
    assert sorted(params) == ["embed", "final_norm", "layer_0", "layer_1",
                              "lm_head"]
    layer = params["layer_1"]
    assert sorted(layer) == ["attn", "mixer_norm", "mixer_post_norm", "mlp",
                             "mlp_norm", "mlp_post_norm"]
    assert layer["attn"]["wq"]["kernel"].shape == (64, 4, 16)
    assert float(jnp.min(layer["mlp_post_norm"]["scale"])) < 0.9


@pytest.mark.parametrize("length", [1, 9, 23])
def test_the_whole_forward_pass_agrees_with_the_reference(length, toy,
                                                          params):
    model = hybrid.HybridLM(model_config(toy))
    tokens = tokens_of(length, 2, length)
    got = jax.jit(model.apply)({"params": params}, tokens)
    want = kit.reference_logits(ref, params, tokens, toy)
    assert got.shape == want.shape == (2, length, 256)
    np.testing.assert_allclose(got, want, atol=2e-5)


def cache_paths(tree) -> list[tuple]:
    return [tuple(k.key for k in path) for path, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


@pytest.mark.parametrize("n, bucket", [(1, 8), (8, 8), (13, 16), (50, 64)])
def test_prefill_of_a_padded_bucket_then_decode_through_the_slot_cache(
        n, bucket, toy, params, slot_programs):
    """A prompt of ``n`` right-padded to ``bucket``, inserted as row 2 of
    a ``DenseSlotCache`` of 3 rows whose last occupant was another
    stream, then 20 tokens decoded there, every pass through leaves of
    its own: every row of logits against the reference's full forward
    pass.  The tree holds passes x layers attention layers, the passes
    after the first under ``pass_<u>``, and after a step each pass's
    keys differ."""
    steps = 20
    cache, _, _ = kit.decode_through_the_slot_cache(
        ref, toy, params, slot_programs(), n, bucket, steps)
    keys = [path for path in cache_paths(cache.tree)
            if path[-1] == "cached_key"]
    assert sorted(keys) == sorted(
        (f"layer_{i}", "attn", *(() if u == 0 else (f"pass_{u + 1}",)),
         "cached_key") for i in range(LAYERS) for u in range(PASSES))
    attn = cache.tree["layer_1"]["attn"]
    by_pass = [attn["cached_key"]] + [attn[f"pass_{u}"]["cached_key"]
                                      for u in range(2, PASSES + 1)]
    for u, keys_u in enumerate(by_pass):
        assert int(attn[kvcache.pass_scope(u)]["cache_index"][2]
                   if u else attn["cache_index"][2]) == n + steps
        assert float(jnp.max(jnp.abs(keys_u[2, n + steps - 1]))) > 0
        for other in by_pass[u + 1:]:
            assert not np.allclose(keys_u[2, :n + steps],
                                   other[2, :n + steps])
    assert cache.stats["attend_layers"] == PASSES * LAYERS
    assert cache._attend_kinds == [(PASSES * LAYERS, 128, 0)]


def test_the_kernel_interpreted_serves_every_pass_and_writes_its_row(
        monkeypatch):
    """Sixteen bfloat16 heads of 128 (Ouro's), 2 layers, 2 passes: a
    decode step through ``hvd.decode_attend`` interpreted, one call a
    (pass, layer), gives the plain form's logits and leaves, the step's
    row written into every pass's own leaves."""
    config = hybrid.HybridConfig(
        vocab_size=64, d_model=64, d_ff=128, layer_types=("attention",) * 2,
        num_heads=16, num_kv_heads=16, attn_head_dim=128,
        attn_rotary_dim=128, attention_multiplier=128 ** -0.5,
        rope_theta=1e6, sandwich_norm=True, loops=2, tie_embeddings=False,
        dtype=jnp.bfloat16, decode=True, max_seq_len=128)
    model = hybrid.HybridLM(config)
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))[
        "params"]
    prompt = tokens_of(7, 3, 16) % 64
    _, cache = kvcache.prefill(model, {"params": params}, prompt,
                               lengths=jnp.int32([16, 9, 3]))
    fed = tokens_of(8, 3, 1) % 64
    plain = kvcache.decode_step(model, {"params": params}, cache, fed)
    calls = []
    real = da._decode_attend_pallas
    monkeypatch.setattr(da, "_decode_attend_pallas",
                        lambda *a, **kw: calls.append(kw) or real(*a, **kw))
    monkeypatch.setattr(kvcache, "decode_attend", functools.partial(
        da.decode_attend, interpret=True))
    kernel = kvcache.decode_step(model, {"params": params}, cache, fed)
    assert len(calls) == 4 and all(kw["block"] == 128 for kw in calls)
    np.testing.assert_allclose(kernel[0].astype(jnp.float32),
                               plain[0].astype(jnp.float32), atol=0.05)
    for path, leaf in jax.tree_util.tree_flatten_with_path(kernel[1])[0]:
        if path[-1].key == "cached_key":
            np.testing.assert_array_equal(
                leaf[jnp.arange(3), jnp.asarray([16, 9, 3])],
                dict(jax.tree_util.tree_flatten_with_path(plain[1])[0])[
                    path][jnp.arange(3), jnp.asarray([16, 9, 3])])


# ------------------------------------------------- loops 1 is what it was
class _UnloopedBlock(nn.Module):
    """``HybridBlock`` as it was before looped stacks: no norm after a
    sub-layer, no pass."""
    cfg: hybrid.HybridConfig
    kind: str

    @nn.compact
    def __call__(self, x, lengths=None):
        cfg = self.cfg
        norm = partial(RMSNorm, cfg.dtype, cfg.param_dtype,
                       cfg.rms_norm_eps)
        mixed = norm(name="mixer_norm")(x)
        if self.kind == "attention":
            mixed = hybrid.GroupedAttention(cfg, name="attn")(mixed, lengths)
        else:
            mixed = hybrid.Mamba2Mixer(cfg, name=self.kind)(mixed, lengths)
        x = x + cfg.residual_multiplier * mixed
        return x + cfg.residual_multiplier * MLP(cfg, name="mlp")(
            norm(name="mlp_norm")(x))


class _UnloopedLM(nn.Module):
    """``HybridLM`` as it was before looped stacks."""
    cfg: hybrid.HybridConfig

    @nn.compact
    def __call__(self, tokens, train=False, lengths=None):
        cfg = self.cfg
        embed = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="embed")
        x = embed(tokens) * cfg.embedding_multiplier
        for i, kind in enumerate(cfg.layer_types):
            x = _UnloopedBlock(cfg, kind, name=f"layer_{i}")(x, lengths)
        x = RMSNorm(cfg.dtype, cfg.param_dtype, cfg.rms_norm_eps,
                    name="final_norm")(x)
        return embed.attend(x) / cfg.logits_scaling


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_one_pass_without_sandwich_norms_is_the_unlooped_model_bit_for_bit(
        dtype):
    """``loops`` 1 and ``sandwich_norm`` false (every accepted
    configuration): the same parameter tree, the same cache tree (no
    pass scope) and bit-equal logits, prefill and a decode step, to the
    decoder as it was, on the hybrid toy configuration (Mamba, attention,
    Mamba)."""
    config = hybrid.HybridConfig(dtype=dtype, logits_scaling=2.0,
                                 residual_multiplier=0.5)
    assert (config.loops, config.sandwich_norm) == (1, False)
    now, then = hybrid.HybridLM(config), _UnloopedLM(config)
    tokens = tokens_of(3, 2, 13)
    params = now.init(jax.random.key(1), tokens)["params"]
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(then.init(jax.random.key(1),
                                               tokens)["params"])
    np.testing.assert_array_equal(
        jax.jit(now.apply)({"params": params}, tokens),
        jax.jit(then.apply)({"params": params}, tokens))
    decoding = dataclasses.replace(config, decode=True, max_seq_len=32)
    seen = []
    for model in (hybrid.HybridLM(decoding), _UnloopedLM(decoding)):
        logits, cache = kvcache.prefill(model, {"params": params}, tokens,
                                        lengths=jnp.int32([13, 6]))
        step, cache = kvcache.decode_step(model, {"params": params}, cache,
                                          tokens[:, :1])
        seen.append((logits, step, cache))
    assert not any(kvcache.later_pass(path)
                   for path in cache_paths(seen[0][2]))
    for got, want in zip(jax.tree_util.tree_leaves(seen[0]),
                         jax.tree_util.tree_leaves(seen[1])):
        np.testing.assert_array_equal(got, want)


# ------------------------------------------------------------- the replica
def test_the_replica_serves_the_references_best_and_counts_every_pass(
        toy, params, solo_world):
    """Six requests over three slots on the normal path: every served
    token is the reference's best (float32); the cache holds passes x
    layers attention layers, counted as such, and the passes after the
    first hold (passes - 1) / passes of its bytes."""
    rng = random.Random(43)
    prompts = [[rng.randrange(2, 256) for _ in range(n)]
               for n in (1, 3, 9, 17, 26, 30)]
    new = [12, 30, 7, 25, 5, 21]
    ex = kit.executor(FAMILY, model_config(toy), params)
    try:
        assert ex.family is hybrid.FAMILY
        stats = ex.stats
        a_layer = 2 * 3 * 64 * 4 * 16 * 4 + 3 * 4     # keys, values, cursors
        assert stats["cache_bytes"] == stats["cache_aliased_bytes"] \
            == PASSES * LAYERS * a_layer
        assert stats["loop_cache_bytes"] == (PASSES - 1) * LAYERS * a_layer
        assert stats["state_bytes"] == stats["window_bytes"] == 0
        assert stats["attend_layers"] == PASSES * LAYERS
        streams = kit.serve(ex, prompts, new)
    finally:
        ex.close()
    assert [len(s) for s in streams] == new
    for prompt, served in zip(prompts, streams):
        assert kit.widest_gap(ref, params, toy, prompt, served) <= 1e-5
    assert 0 < stats["attend_live_positions"] <= stats["attend_read_positions"]


def test_the_loop_cache_share_reads_three_quarters_at_the_published_passes():
    import tracing
    reader = harness.load_json(harness.HERE, "layer_metrics",
                               "replica.loop_cache_share.json")
    cache = 8 * 640 * 192 * 8192 + 192 * 8 * 4
    for facts, want in (
            ({"counters": {"stats.loop_cache_bytes": cache * 3 // 4,
                           "stats.cache_bytes": cache}}, 75.0),
            ({"counters": {"stats.cache_bytes": cache}}, None)):
        got = tracing.evaluate(reader["reader"], facts)
        assert got == (pytest.approx(want) if want else None)


# -------------------------------------------------------------- the counts
def test_the_counts_at_the_published_widths():
    """The reckoning of the configuration's memory and traffic, from
    its own file."""
    cfg = kit.load(FAMILY)
    counts = ouro_counts
    assert counts.layer_params(cfg) == 51_388_416
    assert counts.layer_matmul_params(cfg) == 16_777_216 + 34_603_008
    assert counts.params(cfg) == 48 * 51_388_416 + 201_326_592 + 2048
    assert counts.params(cfg) * 2 == 5_335_945_216
    assert counts.attention_layers(cfg) == 192
    assert counts.row_width(cfg) * 2 == 8192
    assert 192 * 8192 == 1_572_864                 # a position, all passes
    assert 8 * 640 * 1_572_864 == 8_053_063_680
    contexts = [267] * 8
    attend = counts.decode_attend_bytes_per_step(cfg, contexts)
    assert attend == 192 * (8 * 268 * 8192 + 8 * 16 * 128 * 6)
    step = counts.decode_bytes_per_step(cfg, contexts)
    weights = 4 * 48 * 51_388_416 * 2
    assert abs(weights - 19.73e9) < 0.01e9
    assert step == weights + (4 * 2048 + 2048 * 49152 + 8 * 2048) * 2 \
        + 192 * 8 * 268 * 8192
    # What a plain 48-layer model of the same widths would not do.
    plain = weights / 4 + (2048 * 49152 + 8 * 2048) * 2 + 48 * 8 * 268 * 8192
    assert 0.74 < 1 - plain / step < 0.76
    from horovod_tpu.telemetry import perfmodel
    config = hybrid.HybridConfig(**harness.build_args(cfg))
    assert counts.decode_flops_per_step(cfg, contexts) \
        == 8 * perfmodel.hybrid_decode_flops(config, 267)


def test_the_programs_count_of_a_token_counts_every_pass():
    """``hybrid_decode_flops`` of a looped stack: every layer once a pass,
    its attention over its own pass's context; the head once."""
    from horovod_tpu.telemetry import perfmodel
    once = hybrid.HybridConfig(layer_types=("attention",) * 2,
                               vocab_size=100)
    looped = dataclasses.replace(once, loops=3)
    head = 2.0 * once.d_model * once.vocab_size
    for context in (1, 57):
        assert perfmodel.hybrid_decode_flops(looped, context) - head \
            == 3 * (perfmodel.hybrid_decode_flops(once, context) - head)
    assert perfmodel.hybrid_decode_flops(looped, 57) \
        - perfmodel.hybrid_decode_flops(looped, 1) \
        == 3 * 2 * 2.0 * (2 * 4 * 16) * 56


# --------------------------------------------- planted faults, at toy size
def _shared_cache(monkeypatch):
    """(a) Every pass reads and writes the first pass's cache: a pass
    after the first writes its rows where the first did and attends over
    what the passes before it left there."""
    real = hybrid.cached_attention

    def shared(module, q, k, v, *, loop=0, **kw):
        if loop:
            index = module.get_variable("cache", "cache_index")
            module.put_variable("cache", "cache_index", index - q.shape[1])
        return real(module, q, k, v, **kw)
    monkeypatch.setattr(hybrid, "cached_attention", shared)


class _FinalNormOnce(hybrid.HybridLM):
    """(b) The final norm after the last pass only."""

    @nn.compact
    def __call__(self, tokens, train=False, lengths=None):
        cfg = self.cfg
        embed = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="embed")
        x = embed(tokens)
        blocks = [hybrid.HybridBlock(cfg, kind, name=f"layer_{i}")
                  for i, kind in enumerate(cfg.layer_types)]
        for loop in range(cfg.loops):
            for block in blocks:
                x = block(x, lengths, loop)
        x = RMSNorm(cfg.dtype, cfg.param_dtype, cfg.rms_norm_eps,
                    name="final_norm")(x)
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="lm_head")(x)


def _row_not_written(monkeypatch):
    """(e) A decode step's row not written in the passes after the first:
    they attend over it this step and have lost it the next."""
    real, later = hybrid.cached_attention, []

    def marked(module, *a, loop=0, **kw):
        later.append(bool(loop))
        try:
            return real(module, *a, loop=loop, **kw)
        finally:
            later.pop()

    def unwritten(q, keys, values, *a, **kw):
        out = da.decode_attend(q, keys, values, *a, **kw)
        return (out[0], keys, values) if later[-1] else out
    monkeypatch.setattr(hybrid, "cached_attention", marked)
    monkeypatch.setattr(kvcache, "decode_attend", unwritten)


def _final_norm_once(monkeypatch):
    monkeypatch.setattr(hybrid, "FAMILY", dataclasses.replace(
        hybrid.FAMILY, build=_FinalNormOnce))


def _with_args(**changed):
    """A plant that changes the model's arguments wherever the
    configuration's file is read (the program and its replay alike)."""
    def plant(monkeypatch):
        build = harness.build_args
        monkeypatch.setattr(harness, "build_args",
                            lambda config: {**build(config), **changed})
    return plant


# name -> (how it is planted, the numbers it must push over their toy
# limits): the tests plant them at the toy size, a chip script at the
# cell's.
FAULTS = {
    "a_shared_cache": (_shared_cache, {"attend_gap"}),
    "b_final_norm_once": (_final_norm_once, {"gap", "replay_err"}),
    "c_a_pass_short": (_with_args(loops=PASSES - 1), {"gap", "replay_err"}),
    "d_no_post_norms": (_with_args(sandwich_norm=False),
                        {"gap", "replay_err"}),
    "e_row_not_written": (_row_not_written, {"attend_gap"}),
}


KEYS = ("gap", "replay_err", "attend_gap")


def test_the_sound_program_reads_rounding_alone(toy, params):
    seen = kit.compared(ref, toy, params, KEYS)
    limits = toy["served_check"]["limits"]
    assert all(seen[key] <= limits[key] / 10 for key in limits), seen


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_comes_out_over_a_toy_limit(fault, toy, params,
                                                    monkeypatch):
    """The program broken underneath, five ways (the cell's list): every
    pass on the first pass's cache, the final norm once, a pass short,
    no norm after a sub-layer, a decode step's row lost in the passes
    after the first; each over the one of the cell's toy limits that is
    there to catch it (``attend_gap`` holds each pass's attention to its
    own cache apart from the logits, which a stream of random weights
    that repeats one token hardly moves)."""
    plant, must = FAULTS[fault]
    plant(monkeypatch)
    seen = kit.compared(ref, toy, params, KEYS)
    limits = toy["served_check"]["limits"]
    over = {key for key in limits if seen[key] > limits[key]}
    assert must <= over, (seen, limits)


# --------------------------------------------- the benchmark's own comparison
def test_the_cells_control_in_int8_comes_out_not_correct(monkeypatch,
                                                         capsys):
    """``--check control`` of the cell at its rehearsal sizes: the served
    tokens stay inside the toy limits, and the tokens that the reference
    computed in int8 puts first do not."""
    seen = kit.check_control(monkeypatch, capsys, FAMILY, 2147483659)
    assert seen["code"] == 0 and seen["ok"] and not seen["problems"]
    assert seen["served_tokens"] > 150
    assert all(seen[key] <= limit for key, limit in seen["limits"].items())
    over = {key for key, limit in seen["limits"].items()
            if seen["control_" + key] > limit}
    assert "replay_err" in over and "attend_gap" not in over, seen
