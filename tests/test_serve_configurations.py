"""What every serving configuration on the hybrid decoder must show, one
test over the families of ``servekit.FAMILIES``: the seeded weights have
the model's own tree, the traffic tables are their laws' quantiles, the
replica's programs carry the scopes and kernel names a trace selects
them by, and a paged cache refuses recurrent state."""
from __future__ import annotations

import random
import types

import jax
import jax.numpy as jnp
import pytest

import servekit as kit
from horovod_tpu.models import hybrid
from horovod_tpu.serving import slotcache
from servekit import harness, solo_world  # noqa: F401

@pytest.mark.parametrize("family", sorted(kit.FAMILIES))
def test_the_seeded_weights_have_the_models_own_tree(family):
    toy = kit.toy(family)
    model = hybrid.HybridLM(kit.model_config(toy))
    own = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    seeded = kit.seeded(family, toy)
    assert jax.tree_util.tree_structure(own) \
        == jax.tree_util.tree_structure(seeded)
    assert [(leaf.shape, leaf.dtype) for leaf in
            jax.tree_util.tree_leaves(own)] \
        == [(leaf.shape, leaf.dtype) for leaf in
            jax.tree_util.tree_leaves(seeded)]


# family -> (traffic, prompts' law, outputs' law, requests, the buckets,
# the longest request, the token budget over the widest bucket)
TRAFFIC = {
    "mimo": ("mixlen_sat", (512, 8192), (1024, 4096), 64,
             [1024, 2048, 4096, 8192], 11474, 64),
    "axk1": ("longdoc_sat", (2048, 10240), (1024, 4096), 64,
             [3072, 4096, 6144, 8192, 10240], 12168, 64),
    "ouro": ("shortreason_sat", (40, 192), (256, 448), 32,
             [64, 128, 256], 614, 8),
}


@pytest.mark.parametrize("family", sorted(TRAFFIC))
def test_the_traffic_tables_are_the_laws_quantiles(family):
    """Midpoint quantiles of a log-uniform law, shuffled once by the
    configuration's seed, prompts first; they fill the configuration's
    warm-up buckets, every request fits its cache, a client a slot."""
    name, prompt_law, output_law, points, buckets, longest, over = \
        TRAFFIC[family]

    def quantiles(low, high):
        return [round(low * (high / low) ** ((i + 0.5) / points))
                for i in range(points)]
    traffic = harness.load_json(harness.HERE, "traffic", name + ".json")
    table = traffic["requests"]
    prompts, outputs = quantiles(*prompt_law), quantiles(*output_law)
    rng = random.Random(kit.FAMILIES[family].seed)
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    assert table == [list(pair) for pair in zip(prompts, outputs)]
    serve = kit.load(family)["serve"]
    assert traffic["clients"] == serve["max_batch"]
    cfg = types.SimpleNamespace(
        max_seq=serve["max_seq"],
        warmup_buckets=tuple(serve["warmup_buckets"]))
    assert sorted({slotcache.prompt_bucket(cfg, p) for p, _ in table}) \
        == serve["warmup_buckets"] == buckets
    assert max(p + o for p, o in table) == longest <= serve["max_seq"]
    assert serve["token_budget"] == max(buckets) + over


# family -> (the decode program's scopes, the prefill's, its bucket)
SCOPES = {
    "granite": (("hvd.ssm_update", "hvd.ssm_conv", "hvd.decode_attend"),
                ("hvd.ssm_scan", "hvd.ssm_conv", "hvd.decode_attend"), 8),
    "solar": (("hvd.kda_update", "hvd.kda_conv", "hvd.moe_route"),
              ("hvd.kda_scan", "hvd.kda_conv", "hvd.moe_route"), 8),
    "mimo": (("hvd.window_attend", "hvd.decode_attend", "hvd.moe_route"),
             ("hvd.prefill_attend", "hvd.decode_attend", "hvd.moe_route"),
             16),
    "axk1": (("hvd.mla_decode", "hvd.moe_route"),
             ("hvd.prefill_attend", "hvd.moe_route"), 16),
}


@pytest.mark.parametrize("family", sorted(SCOPES))
def test_the_programs_carry_the_scope_and_kernel_names(family, solo_world):
    """The scopes are in the replica's programs' debug info and nowhere
    in the programs themselves; hvd.sample, which reached no device
    event, is gone."""
    decode_scopes, prefill_scopes, bucket = SCOPES[family]
    ex = kit.executor(family, kit.model_config(kit.toy(family)),
                      warmup_buckets=())
    try:
        decode, args = ex.cache._decode_call(
            ex.params, ex._last_tokens, ex._token_on_host)
        decode = decode.lower(*args)
        prefill = ex.cache._prefill_jit.lower(
            ex.params, jnp.zeros((1, bucket), jnp.int32), jnp.int32(3))
        for program, scopes in ((decode, decode_scopes),
                                (prefill, prefill_scopes)):
            named = program.as_text(debug_info=True)
            for scope in scopes:
                assert scope in named, scope
            assert "hvd.sample" not in named
            assert "hvd." not in program.as_text()
    finally:
        ex.close()


@pytest.mark.parametrize("family", ["granite", "solar"])
def test_a_paged_cache_cannot_hold_recurrent_state_yet(family, solo_world):
    with pytest.raises(ValueError, match="recurrent state in KVBlockPool"):
        kit.executor(family, kit.model_config(kit.toy(family)), paged=True)
