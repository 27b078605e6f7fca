"""Solar Open 2 on the hybrid decoder: the delta-rule recurrence in its
two forms, the routed expert layer and its share of a deployment, the
model through the slot cache and the replica, all against the
benchmark's plain float32 reference (benchmarks/chip/
solar_open2_reference.py) on its seeded weights, comparing logits.  Toy
widths: the rehearsal sizes of the configuration's own file."""
from __future__ import annotations

import random
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import servekit as kit
from horovod_tpu.models import hybrid, kvcache, moe
from horovod_tpu.ops import kda
from servekit import harness, model_config, solo_world, tokens_of  # noqa: F401
from test_decode_attention import pallas_calls

FAMILY = "solar"
ref, solar_open2_counts = kit.module(FAMILY), kit.module(FAMILY, "counts")
toy, params = kit.fixtures(FAMILY)


# ------------------------------------------------------------- the recurrence
def operands(seed: int, b: int, t: int, h: int, d: int):
    """q and k normalised a head, a log decay that forgets fast in some
    channels (to -12 a position), a write strength on 0 to 2."""
    keys = jax.random.split(jax.random.key(seed), 5)
    q, k, v = (jax.random.normal(key, (b, t, h, d)) for key in keys[:3])
    q, k = (x / jnp.linalg.norm(x, axis=-1, keepdims=True) for x in (q, k))
    g = -jnp.exp(jax.random.uniform(keys[3], (b, t, h, d), minval=-6.0,
                                    maxval=2.5))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[4], (b, t, h)))
    return q * d ** -0.5, k, v, g, beta


def positionwise(q, k, v, g, beta):
    """The equations, one position at a time: (o [B, T, H, V], the state
    after every position [T, B, H, K, V])."""
    def step(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = jnp.exp(g_t)[..., None] * state
        eye = jnp.eye(k_t.shape[-1])
        state = jnp.einsum(
            "bhkc,bhcv->bhkv",
            eye - b_t[..., None, None] * k_t[..., :, None] * k_t[..., None, :],
            state) + b_t[..., None, None] * k_t[..., :, None] \
            * v_t[..., None, :]
        return state, (jnp.einsum("bhkv,bhk->bhv", state, q_t), state)

    b, _, h, d = k.shape
    _, (o, states) = jax.lax.scan(
        step, jnp.zeros((b, h, d, v.shape[-1])),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), states


@pytest.mark.parametrize("length,chunk", [(1, 8), (7, 8), (8, 8), (9, 8),
                                          (23, 8), (64, 16)])
def test_kda_scan_agrees_with_the_positionwise_recurrence(length, chunk):
    """The chunked form against S_t = (I - b k k^T) Diag(a) S_{t-1} +
    b k v^T written as it stands: under a chunk, on and off its edge."""
    args = operands(length, 2, length, 4, 16)
    with jax.default_matmul_precision("highest"):
        o, state = jax.jit(lambda *a: kda.kda_scan(*a, chunk=chunk))(*args)
        want_o, states = positionwise(*args)
    np.testing.assert_allclose(o, want_o, atol=2e-6)
    np.testing.assert_allclose(state, states[-1], atol=5e-6)


def test_kda_scan_stops_at_each_rows_length():
    """With ``lengths`` the state is the one after each row's last real
    position, whatever the padding holds, and the output up to there is
    the unpadded one."""
    args = operands(5, 3, 23, 4, 16)
    lengths = jnp.array([5, 17, 23])
    with jax.default_matmul_precision("highest"):
        o, state = jax.jit(lambda *a: kda.kda_scan(
            *a, chunk=8, lengths=lengths))(*args)
        want_o, states = positionwise(*args)
    for row, n in enumerate([5, 17, 23]):
        np.testing.assert_allclose(state[row], states[n - 1, row],
                                   atol=5e-6)
        np.testing.assert_allclose(o[row, :n], want_o[row, :n], atol=2e-6)


@pytest.mark.parametrize("heads,d,block_heads", [(8, 16, 8), (8, 16, 4),
                                                 (4, 128, 4)])
def test_kda_update_interpreted_agrees_with_the_plain_form(heads, d,
                                                           block_heads):
    """hvd.kda_update, interpreted, against kda_update_plain and against
    one position of the equations; the state's layout is the equations'."""
    q, k, v, g, beta = (x[:, 0] for x in operands(9, 3, 1, heads, d))
    state = jax.random.normal(jax.random.key(1), (3, heads, d, d))
    o, new = kda.kda_update(state, q, k, v, g, beta,
                            block_heads=block_heads, interpret=True)
    want_o, want_new = kda.kda_update_plain(state, q, k, v, g, beta)
    np.testing.assert_allclose(o, want_o, atol=2e-6)
    np.testing.assert_allclose(new, want_new, atol=2e-6)
    decayed = jnp.exp(g)[..., None] * state
    by_hand = decayed - beta[..., None, None] * k[..., :, None] \
        * jnp.einsum("bhk,bhkv->bhv", k, decayed)[..., None, :] \
        + beta[..., None, None] * k[..., :, None] * v[..., None, :]
    np.testing.assert_allclose(new, by_hand, atol=2e-6)


def test_kda_update_writes_the_state_in_place_under_its_own_name():
    """One pallas_call named hvd.kda_update whose first operand, the
    state, is aliased to its second result."""
    q, k, v, g, beta = (x[:, 0] for x in operands(2, 2, 1, 4, 16))
    jaxpr = jax.make_jaxpr(lambda s: kda.kda_update(
        s, q, k, v, g, beta, interpret=True))(jnp.zeros((2, 4, 16, 16)))
    call, = pallas_calls(jaxpr.jaxpr)
    assert tuple(call.params["input_output_aliases"]) == ((0, 1),)
    assert call.params["name"] == "hvd.kda_update"
    assert call.invars[0].aval.shape == call.outvars[1].aval.shape


def test_the_expert_products_run_under_one_kernel_name():
    layer = routed((4, 4), interpret=True)
    x = jnp.zeros((1, 5, 64))
    params = jax.eval_shape(lambda: layer.init(jax.random.key(0), x))
    jaxpr = jax.make_jaxpr(lambda p: layer.apply(p, x))(params)
    call, = pallas_calls(jaxpr.jaxpr)
    assert call.params["name"] == "hvd.moe_experts"


def test_off_the_tpu_the_plain_forms_run():
    assert not kda._on_tpu() and not moe._on_tpu()
    q, k, v, g, beta = (x[:, 0] for x in operands(2, 2, 1, 4, 16))
    jaxpr = str(jax.make_jaxpr(kda.kda_update)(
        jnp.zeros((2, 4, 16, 16)), q, k, v, g, beta))
    assert "pallas_call" not in jaxpr


# ------------------------------------------------------------ the routed layer
def routed(held, interpret=False, **kw):
    return moe.RoutedExperts(**{**dict(
        num_experts=16, per_token=2, d_ff=32, held=held,
        dtype=jnp.float32, interpret=interpret), **kw})


def loop_over_experts(params, x, per_token, held):
    """A loop over the held experts with a mask, the shared expert once."""
    first, count = held
    tokens = x.reshape(-1, x.shape[-1])
    scores = jax.nn.sigmoid(tokens @ params["router"])
    top, chosen = jax.lax.top_k(scores, per_token)
    top = top / top.sum(-1, keepdims=True)
    mlp = lambda g, u, d: (jax.nn.silu(tokens @ g) * (tokens @ u)) @ d  # noqa
    y = mlp(*(params["shared_" + n]["kernel"] for n in ("gate", "up", "down")))
    for e in range(count):
        weight = jnp.sum(jnp.where(chosen == first + e, top, 0.0), -1)
        y = y + weight[:, None] * mlp(*(params["experts_" + n][e]
                                        for n in ("gate", "up", "down")))
    return y.reshape(x.shape), chosen


@pytest.mark.parametrize("interpret", [False, True])
@pytest.mark.parametrize("held", [(0, 4), (4, 4), (12, 4), (0, 16)])
def test_the_routed_layer_agrees_with_a_loop_over_its_experts(held,
                                                              interpret):
    """Grouped products over the pairs sorted by expert (lax.ragged_dot,
    and hvd.moe_experts interpreted) against the loop, **under a skewed
    router**: every token wants expert 5, far beyond any capacity, and
    none is dropped; the counters count what was routed."""
    layer = routed(held, interpret)
    x = jax.random.normal(jax.random.key(1), (2, 9, 64)).at[..., 0].set(3.0)
    params = layer.init(jax.random.key(0), x)["params"]
    skew = params["router"].at[0].set(0.0).at[0, 5].set(4.0)
    params = {**params, "router": skew}
    with jax.default_matmul_precision("highest"):
        y, sown = jax.jit(lambda p, x: layer.apply(
            {"params": p}, x, mutable=["counters"]))(params, x)
        want, chosen = loop_over_experts(params, x, 2, held)
    np.testing.assert_allclose(y, want, atol=5e-6)
    first, count = held
    here = (chosen >= first) & (chosen < first + count)
    assert int(jnp.sum(chosen == 5)) == 18          # every token's first
    counted = {name: int(value[0])
               for name, value in sown["counters"].items()}
    assert counted == {
        "moe_routed_pairs": 36, "moe_local_pairs": int(jnp.sum(here)),
        "moe_experts_touched": len(set(np.asarray(chosen)[np.asarray(here)]
                                       .tolist())),
        "moe_expert_slots": count}


def test_the_rows_grow_with_the_pairs_routed_here_not_with_the_experts():
    """512 tokens, top-8 of 320, 40 held: the live tiles hold the local
    pairs (some 512, each group padded to whole tiles), not tokens x
    experts held; and a step that routes nothing here has no live tile."""
    scores = jax.random.uniform(jax.random.key(0), (512, 320))
    _, local, here = moe.route(scores, 8, (0, 40))
    tile = moe.tile_rows(512, 8, 320)
    assert tile == 32 and moe.tile_rows(80, 8, 320) == 16
    row_token, at, tile_expert, tiles, sizes = moe.group_rows(
        local, here, 40, tile)
    pairs = int(jnp.sum(here))
    assert 400 < pairs < 640 and int(jnp.sum(sizes)) == pairs
    assert pairs <= int(tiles[0]) * tile < pairs + 40 * tile < 20480
    rows = np.asarray(at)[np.asarray(here)]
    assert len(set(rows.tolist())) == pairs         # a row a pair
    np.testing.assert_array_equal(
        np.asarray(row_token)[rows],
        np.nonzero(np.asarray(here))[0])            # of its own token
    np.testing.assert_array_equal(                  # in its expert's tiles
        np.asarray(tile_expert)[rows // tile],
        np.asarray(local)[np.asarray(here)])
    _, _, _, none, _ = moe.group_rows(local, jnp.zeros_like(here), 40, tile)
    assert int(none[0]) == 0


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer(toy):
    """The guide's section 4: 16 experts over 4 chips, 4 held each.  The
    partial results of all 4 shares, with the shared expert counted
    once, add up to what the reference gives the layer with every expert
    in one place; through the program's layer and the reference's."""
    cfg = {**toy, "experts_held": [0, 16], "n_routed_experts": 16}
    whole = kit.seeded_layer(FAMILY, cfg)
    x = jax.random.normal(jax.random.key(3), (2, 11, 64))
    with jax.default_matmul_precision("highest"):
        uncut = ref.experts(
            {"mlp_norm": {"scale": jnp.ones(64)}, "moe": whole["moe"]},
            x, cfg) - x
        normed = x * jax.lax.rsqrt(
            jnp.mean(x * x, -1, keepdims=True) + cfg["rms_norm_eps"])
        shared = ref.gated_mlp(normed.reshape(-1, 64), *(
            whole["moe"]["shared_" + n]["kernel"]
            for n in ("gate", "up", "down"))).reshape(x.shape)
        total_program, total_reference = shared, shared
        for first in (0, 4, 8, 12):
            held = (first, 4)
            mine = kit.seeded_layer(FAMILY, toy, experts_held=list(held))
            for name in ("experts_gate", "experts_up", "experts_down"):
                np.testing.assert_array_equal(
                    mine["moe"][name], whole["moe"][name][first:first + 4])
            layer = routed(held, shared=0)
            own = {k: v for k, v in mine["moe"].items()
                   if not k.startswith("shared_")}
            total_program = total_program + layer.apply(
                {"params": own}, normed)
            total_reference = total_reference + ref.experts_share(
                mine["moe"], normed.reshape(-1, 64), toy, held
            ).reshape(x.shape)
    np.testing.assert_allclose(total_reference, uncut, atol=5e-6)
    np.testing.assert_allclose(total_program, uncut, atol=5e-6)


# ------------------------------------------------------------------ the model
def test_the_toy_is_a_gated_softmax_layer_then_three_kda_layers(toy,
                                                                 params):
    """The rehearsal sizes: hidden 64; one gated softmax layer of 4 query
    heads over 2 key-value heads of 32, then three KDA layers of 4 heads
    of 16, chunks of 8; 16 experts of width 32, top-2, experts 4 to 7
    held; vocabulary 256; an untied head."""
    assert "lm_head" in params and "wg" in params["layer_0"]["attn"]
    assert [kind for kind in toy["layer_types"]] \
        == ["attention", "kda", "kda", "kda"]


@pytest.mark.parametrize("length", [1, 3, 8, 9, 23])
def test_the_whole_forward_pass_agrees_with_the_reference(length, toy,
                                                          params):
    model = hybrid.HybridLM(model_config(toy))
    tokens = tokens_of(length, 2, length)
    got = jax.jit(model.apply)({"params": params}, tokens)
    want = kit.reference_logits(ref, params, tokens, toy)
    assert got.shape == want.shape == (2, length, 256)
    np.testing.assert_allclose(got, want, atol=2e-5)


programs = kit.programs_fixture(max_seq_len=32, interpret=True)


@pytest.mark.parametrize("n", [1, 3, 8, 13])
def test_prefill_of_a_padded_bucket_then_decode_through_the_cache(
        n, toy, params, programs):
    """A prompt of ``n`` in a bucket of 16 with ``lengths = n``, then
    decode steps in slot 2 of a four-slot cache, the kernels interpreted:
    every logit row against the reference's full pass, and the delta-rule
    state after prefill equal to the reference's at ``n`` (which fails if
    the recurrence runs into the padding)."""
    assert programs[0] is hybrid.ROUTED_FAMILY
    row, tokens, counts = kit.prefill_then_decode(ref, toy, params,
                                                  programs, n, atol=2e-5)
    for counted in counts:
        # Four slots, top-2, four layers of four held experts.
        assert int(counted[0]) == 32 and int(counted[3]) == 16
        assert 0 <= int(counted[2]) <= int(counted[1]) <= 32

    with jax.default_matmul_precision("highest"):
        def first_kda(p, t):
            x = ref.attention(p["layer_0"], ref.embed(p, t, toy), toy)
            x = ref.experts(p["layer_0"], x, toy)
            return ref.kda(p["layer_1"], x, toy, state_at=n)[1]
        state = jax.jit(first_kda)(params, tokens[:, :n])
    stored = row["layer_1"]["kda"]["kda_state"]
    assert stored.shape == (1, 4, 16, 16) and stored.dtype == jnp.float32
    np.testing.assert_allclose(stored, state, atol=2e-6)
    assert row["layer_1"]["kda"]["conv_state"].shape == (1, 3, 3 * 64)


def test_granites_toy_model_is_what_it_was():
    """The edits to models/hybrid.py leave the second family's first
    member alone: the same parameter tree, and, to the bit, the logits of
    the formulas as they stood (the convolution written out here as
    Mamba2Mixer had it before it was shared with KDAMixer)."""
    cfg = kit.load("granite")
    cfg = harness.merged(cfg, cfg["rehearsal"])
    weights = kit.seeded("granite", cfg)
    config = hybrid.HybridConfig(**harness.build_args(cfg))
    assert config.family is hybrid.FAMILY and config.head_dim == 16
    assert not hybrid.FAMILY.decode_counters
    model = hybrid.HybridLM(config)
    own = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    assert jax.tree_util.tree_structure(own) \
        == jax.tree_util.tree_structure(weights)
    tokens = tokens_of(29, 2, 19)
    got = jax.jit(model.apply)({"params": weights}, tokens)

    def old_conv(module, cfg, x, kernel, bias, lengths, *, cached,
                 stepping, scope):
        b, t, channels = x.shape
        width = kernel.shape[0]
        padded = jnp.concatenate(
            [jnp.zeros((b, width - 1, channels), x.dtype), x], axis=1)
        conv = sum(padded[:, i:i + t].astype(jnp.float32)
                   * kernel[i].astype(jnp.float32)
                   for i in range(width)) + bias.astype(jnp.float32)
        return jax.nn.silu(conv).astype(cfg.dtype)

    new_conv = hybrid._windowed_conv
    hybrid._windowed_conv = old_conv
    try:
        was = jax.jit(hybrid.HybridLM(config).apply)(
            {"params": weights}, tokens)
    finally:
        hybrid._windowed_conv = new_conv
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(was, np.float32))
    assert got.dtype == jnp.bfloat16 and float(jnp.max(jnp.abs(got))) > 0


def test_the_counts_at_the_published_widths():
    """The ISSUE's arithmetic, from the configuration's own file under
    even routing; the expert kernel's roofline takes what a step would
    move if every held expert were touched and every pair were local,
    times the shares that the program counted."""
    cfg = kit.load(FAMILY)
    counts = solar_open2_counts
    assert "moe_experts_touched_share" not in cfg       # measured, not set
    assert counts.moe_held_expert_bytes_per_step(cfg, [1024] * 80) \
        == 4 * 40 * 15_728_640 * 2
    assert counts.moe_routed_row_bytes_per_step(cfg, [1024] * 80) \
        == 4 * 80 * 8 * 4096 * 6
    reader = harness.load_json(
        harness.HERE, "layer_metrics", "kernels.moe_experts_roofline.json")
    import tracing
    facts = {"counters": {
        "moe_held_expert_bytes_per_step": 1000.0,
        "moe_routed_row_bytes_per_step": 80.0,
        "stats.moe_experts_touched": 3, "stats.moe_expert_slots": 4,
        "stats.moe_local_pairs": 1, "stats.moe_routed_pairs": 8},
        "peaks": {"hbm_bytes_per_s": 1e6},
        "metrics": {"kernels.moe_experts_device_ms_per_step": 2.0}}
    assert tracing.evaluate(reader["reader"], facts) \
        == pytest.approx(100.0 * (750.0 + 10.0) / 1e6 / 2e-3)
    del facts["counters"]["stats.moe_experts_touched"]   # the parent
    assert tracing.evaluate(reader["reader"], facts) is None
    assert counts.layers(cfg) == (3, 1)
    assert counts.expert_params(cfg) == 15_728_640
    full = [1024] * 80
    assert abs(counts.experts_touched(cfg, 80) - 34.7) < 0.05
    assert counts.local_pairs(cfg, 80) == 80.0
    assert counts.kda_sizes(cfg)[2] * 4 * 3 == 12_582_912    # a slot
    state = counts.kda_update_bytes_per_step(cfg, full)
    assert 2.01e9 < state < 2.06e9
    experts = counts.moe_expert_bytes_per_step(cfg, full)
    assert 4.36e9 < experts < 4.39e9
    assert experts < 4 * 40 * counts.expert_params(cfg) * 2   # never all
    assert 1.37e9 < counts.dense_params(cfg) * 2 < 1.40e9
    total = counts.decode_bytes_per_step(cfg, full)
    assert 8.1e9 < total < 8.6e9
    assert 0.74 < (experts + state) / total < 0.80
    short = counts.decode_bytes_per_step(cfg, [16] * 80)
    assert total - short == 80 * 1008 * 2 * 8 * 128 * 2
    # The program's own count of one generated token is this chip's: of
    # the 8 experts a token takes, the share held here.
    from horovod_tpu.telemetry import perfmodel
    config = hybrid.HybridConfig(**harness.build_args(cfg))
    assert abs(80 * perfmodel.hybrid_decode_flops(config, 1024)
               / counts.decode_flops_per_step(cfg, full) - 1.0) < 1e-6


def test_a_step_hands_out_what_its_layers_sowed_and_stays_a_pair(toy,
                                                                 params):
    """``decode_step`` and ``prefill`` return (logits, cache) whatever is
    asked; a dict given as ``sown`` receives the collections it names: a
    layer's counters (summed by name over the layers; the first family
    names none) and the experts each token took."""
    config = model_config(toy, decode=True, max_seq_len=32)
    family = config.family
    model = family.build(config)
    tokens = tokens_of(5, 1, 6)
    sown = {"routing": {}}
    logits, cache = family.prefill(model, {"params": params}, tokens,
                                   lengths=6, sown=sown)
    took = sown["routing"]["layer_2"]["moe"]["chosen"][0]
    assert took.shape == (6, 2) and 0 <= int(took.min()) \
        and int(took.max()) < 16
    plain = family.prefill(model, {"params": params}, tokens, lengths=6)
    np.testing.assert_array_equal(plain[0], logits)
    sown = {"counters": {}, "routing": {}}
    pair = family.decode_step(model, {"params": params}, cache,
                              tokens[:, :1], sown=sown)
    assert len(pair) == 2 and set(sown) == {"counters", "routing"}
    counts = jnp.concatenate(kvcache.summed(sown["counters"],
                                            family.decode_counters))
    assert counts.dtype == jnp.int32 and counts.shape == (4,)
    assert int(counts[0]) == 1 * 2 * 4 and int(counts[3]) == 16
    assert kvcache.summed({}, hybrid.FAMILY.decode_counters) == []


def test_the_slot_cache_fetches_tokens_and_counts_what_rides_behind():
    from horovod_tpu.serving.slotcache import _SlotCache
    cache = _SlotCache.__new__(_SlotCache)
    cache.cfg = types.SimpleNamespace(slots=3)
    cache.family = types.SimpleNamespace(decode_counters=("a", "b"))
    cache.stats = {"a": 1, "b": 0}
    got = cache.fetch(jnp.asarray([7, 8, 9, 20, 30], jnp.int32))
    assert got.tolist() == [7, 8, 9] and cache.stats == {"a": 21, "b": 30}
    cache.family = types.SimpleNamespace(decode_counters=())
    assert cache.fetch(np.asarray([1, 2, 3])).tolist() == [1, 2, 3]
    assert cache.stats == {"a": 21, "b": 30}


@pytest.mark.parametrize("tie,followed", [(0.0, False), (0.05, True),
                                          (0.2, True)])
def test_the_reference_follows_a_choice_only_where_its_scores_tie(
        tie, followed):
    """Three experts, top-2.  The scores' own choice is {0, 1}; the
    program took {0, 2}, whose expert 2 lies 0.04 under the cut: followed
    where ``tie`` is wider than that, with the weights of the scores
    themselves.  A choice 0.5 under the cut is followed by none."""
    cfg = {"num_experts_per_tok": 2, "norm_topk_prob": True,
           "routed_scaling_factor": 1.0}
    scores = jnp.asarray([[0.9, 0.6, 0.56, 0.1], [0.9, 0.6, 0.56, 0.1]])
    follow = jnp.asarray([[0, 2], [0, 3]])
    top, chosen, flipped, margin = ref.routing(scores, cfg, follow, tie)
    assert sorted(chosen[0].tolist()) == ([0, 2] if followed else [0, 1])
    assert sorted(chosen[1].tolist()) == [0, 1]
    assert flipped.tolist() == [followed, False]
    np.testing.assert_allclose(margin, [0.04, 0.5], atol=1e-6)
    other = 0.56 if followed else 0.6
    np.testing.assert_allclose(sorted(top[0].tolist()),
                               sorted([0.9 / (0.9 + other),
                                       other / (0.9 + other)]), atol=1e-6)
    own = ref.routing(scores, cfg)
    assert not own[2].any() and sorted(own[1][0].tolist()) == [0, 1]


@pytest.mark.parametrize("at", [1, 5, 12])
def test_the_reference_scan_keeps_the_state_after_a_traced_position(
        at, toy, params):
    """``state_at`` may be traced (a request's length is), the state kept
    is the one after that many positions, and ``state_dtype`` rounds it
    after every position: bfloat16 moves it by parts in a thousand."""
    x = ref.embed(params, tokens_of(7, 1, 12), toy)
    with jax.default_matmul_precision("highest"):
        kept = jax.jit(lambda n: ref.kda(
            params["layer_1"], x, toy, state_at=n)[1])(jnp.int32(at))
        short = ref.kda(params["layer_1"], x[:, :at], toy, state_at=at)[1]
        rounded = ref.kda(params["layer_1"], x, toy, state_at=at,
                          state_dtype=jnp.bfloat16)[1]
    np.testing.assert_allclose(kept, short, atol=1e-6)
    apart = float(jnp.linalg.norm(rounded - kept) / jnp.linalg.norm(kept))
    assert 1e-4 < apart < 2e-2


def test_the_replay_shows_the_logits_the_routing_and_the_final_state(
        toy, params):
    """A stream of 5 prompt and 9 served positions replayed through the
    program (float32 at toy size): its logits are the reference's at
    every live position and zeros elsewhere, the experts it took are the
    reference's own, and the slot's final state is the reference scan's
    after all but the last token."""
    first, length, pad = 5, 14, 128
    tokens = jnp.zeros((1, pad), jnp.int32).at[:, :length].set(
        tokens_of(11, 1, length))
    program = ref.replay(toy)(params, tokens, np.int32(first),
                              np.int32(length))
    want = kit.reference_logits(ref, params, tokens[:, :length], toy)[0]
    live = slice(first - 1, length - 1)
    np.testing.assert_allclose(program["logits"][live], want[live],
                               atol=2e-5)
    assert not np.asarray(program["logits"][:first - 1]).any() \
        and not np.asarray(program["logits"][length - 1:]).any()
    assert program["chosen"].shape == (4, pad, 2)
    assert sorted(program["states"]) == sorted(program["fed"]) == [1, 2, 3]
    assert {name: fed.shape for name, fed in program["fed"][2].items()} \
        == {"k": (1, pad, 4, 16), "v": (1, pad, 4, 16),
            "g": (1, pad, 4, 16), "beta": (1, pad, 4)}
    with jax.default_matmul_precision("highest"):
        x = ref.embed(params, tokens[:, :length], toy)
        x = ref.experts(params["layer_0"],
                        ref.attention(params["layer_0"], x, toy), toy)
        state = ref.kda(params["layer_1"], x, toy, state_at=length - 1)[1]
    np.testing.assert_allclose(program["states"][1], state[0], atol=2e-6)
    # The comparison on this stream: the routing it follows is the
    # reference's own, and state and logits agree to rounding.
    seen = ref.served_gap(toy)(params, tokens, np.int32(first),
                               np.int32(length))
    assert float(seen["state_gap"]) < 1e-6 and float(seen["state_err"]) < 1e-5
    assert float(seen["replay_err"]) < 1e-4
    assert int(seen["route_flips_sum"]) == 0 \
        and float(seen["route_margin"]) == 0.0
    assert int(seen["replay_miss_sum"]) > 0     # random tokens, not served
    with pytest.raises(ValueError, match="bucket"):
        ref.replay(toy)(params, tokens, np.int32(65), np.int32(70))


# ---------------------------------------------------------------- the replica
def test_the_replica_serves_the_references_best_and_counts_the_routing(
        toy, params, solo_world):
    """Seven requests over three slots on the normal path: every served
    token is the reference's best (float32), the delta-rule state is
    counted as state and updated in place, and the routing counters come
    back with the tokens: pairs = decode dispatches x 3 slots x top-2 x 4
    layers."""
    rng = random.Random(34)
    prompts = [[rng.randrange(2, 256) for _ in range(n)]
               for n in (1, 2, 5, 8, 9, 13, 16)]
    new = [9, 4, 7, 12, 5, 8, 6]
    ex = kit.executor(FAMILY, model_config(toy), params)
    try:
        assert ex.family is hybrid.ROUTED_FAMILY
        stats = ex.stats
        assert stats["state_bytes"] < stats["cache_bytes"] \
            == stats["cache_aliased_bytes"]
        assert "kv_bytes" not in stats     # cache_bytes less state_bytes
        a_layer = 4 * 16 * 16 * 4 + 3 * (3 * 64) * 4
        assert stats["state_bytes"] == 3 * 3 * a_layer
        # Warm-up's step is not fetched by the serve loop: not counted.
        assert all(stats[name] == 0 for name in moe.COUNTERS)
        streams = kit.serve(ex, prompts, new)
        dispatches = stats["steps"]["decode"] + stats["steps"]["admit"]
    finally:
        ex.close()
    assert [len(s) for s in streams] == new
    for prompt, served in zip(prompts, streams):
        assert kit.widest_gap(ref, params, toy, prompt, served) <= 1e-5
    routed_steps = stats["moe_routed_pairs"] // (3 * 2 * 4)
    assert 0 < routed_steps <= dispatches
    assert stats["moe_expert_slots"] == routed_steps * 16
    assert 0 < stats["moe_experts_touched"] <= stats["moe_local_pairs"] \
        < stats["moe_routed_pairs"]


# --------------------------------------------- the benchmark's own comparison
@pytest.mark.parametrize("seed", [3, 2147483659, 2000000011])
def test_the_cells_control_in_int8_comes_out_not_correct(seed, monkeypatch,
                                                         capsys):
    """``--check control`` of the new cell at its rehearsal sizes: the
    served tokens, their replay and the slot's final state stay inside
    the toy limits, and the reference computed in the precision below
    (linear maps in int8, the state in bfloat16) does not, by any of
    them."""
    seen = kit.check_control(monkeypatch, capsys, FAMILY, seed)
    assert seen["code"] == 0 and seen["ok"] and not seen["problems"]
    assert seen["served_tokens"] > 400
    assert all(seen[key] <= limit < seen["control_" + key]
               for key, limit in seen["limits"].items())


def state_in_bfloat16(monkeypatch):
    """The delta-rule state rounded to bfloat16 after every step."""
    def rounded(fn):
        def run(*args, **kw):
            o, state = fn(*args, **kw)
            return o, jax.lax.reduce_precision(state, 8, 7)
        return run
    monkeypatch.setattr(kda, "kda_scan", rounded(kda.kda_scan))
    monkeypatch.setattr(kda, "kda_update", rounded(kda.kda_update))


def an_expert_left_out(monkeypatch):
    """The contribution of the first expert held left out."""
    route = moe.route

    def without_the_first(scores, per_token, held, **kw):
        weights, local, here = route(scores, per_token, held, **kw)
        return weights, local, here & (local != 0)
    monkeypatch.setattr(moe, "route", without_the_first)


def b_without_its_2(monkeypatch):
    """The write strength without its factor 2."""
    monkeypatch.setattr(hybrid, "_WRITE_SCALE", 1.0)


TOKENS = {"gap", "gap_mean", "replay_err"}
# name -> (how it is planted, the numbers it pushes over their limits)
FAULTS = {
    "state_in_bfloat16": (state_in_bfloat16, {"state_gap", "replay_err"}),
    "an_expert_left_out": (an_expert_left_out, TOKENS),
    "b_without_its_2": (b_without_its_2, TOKENS),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_comes_out_over_a_toy_limit(fault, toy, params,
                                                    monkeypatch):
    """The program broken underneath, three ways, on one stream that it
    serves and its replay: by ``state_gap`` alone of the numbers of a
    state, which the other faults leave alone (they feed the recurrence
    something else, and it carries that as it should; and at this size,
    in float32, by the replay's logits), and by the logits' numbers."""
    plant, over = FAULTS[fault]
    plant(monkeypatch)
    seen = kit.compared(ref, toy, params, ("gap", "replay_err", "state_gap"))
    limits = toy["served_check"]["limits"]
    assert {key for key in seen if seen[key] > limits[key]} == over, seen


def no_attention_gate(monkeypatch):
    """The softmax layer's gate left out of the replica's model alone."""
    configured = harness.Run.model_config
    monkeypatch.setattr(
        harness.Run, "model_config", lambda self, **over: configured(
            self, **{**over, "attn_gate": False}))


# name -> (how it is planted, the numbers it pushes over their limits): a
# fault that only the replica's served streams show
SERVED = {"no_attention_gate": (no_attention_gate,
                                {"gap_mean", "replay_miss_mean"})}


@pytest.mark.parametrize("fault", sorted(SERVED))
def test_a_planted_fault_comes_out_not_correct(fault, monkeypatch, capsys):
    """The timed path broken underneath where the replay, which builds its
    model from the configuration's file, still has it right: it no
    longer reproduces the streams served.  The run ends, and ``correct``
    is false by the comparison with the reference alone."""
    plant, over = SERVED[fault]
    plant(monkeypatch)
    seen = kit.check_control(monkeypatch, capsys, FAMILY, 2147483659)
    assert seen["code"] == 1 and not seen["ok"]
    assert seen["problems"] and all("below the reference" in p
                                    for p in seen["problems"])
    assert {key for key, limit in seen["limits"].items()
            if seen[key] > limit} == over
