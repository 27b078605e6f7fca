"""What the tests of the serving configurations share: a configuration's
toy, its seeded weights and its reference's logits, a replica in a world
of one, a stream served through the slot cache or greedily, the
benchmark's own ``--check control`` and the replay-level comparison.

A configuration's test file holds its kernels, its equations and its
faults; it names its family in ``FAMILIES`` and takes its fixtures from
``fixtures``.  pytest collects no test here (the name does not start
with ``test_``)."""
from __future__ import annotations

import copy
import dataclasses
import importlib
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (os.path.join(REPO, "benchmarks", "chip"), REPO):
    if path not in sys.path:
        sys.path.insert(0, path)

import run as harness  # noqa: E402

from horovod_tpu.models import hybrid, kvcache  # noqa: E402
from horovod_tpu.serving import slotcache  # noqa: E402

F32 = {"dtype": "@jax.numpy:float32", "param_dtype": "@jax.numpy:float32"}


@dataclasses.dataclass(frozen=True)
class Family:
    """One serving configuration under test."""
    config: str                # its file under benchmarks/chip/configs
    cell: str                  # the cell whose ``--check control`` is run
    reference: str             # the module of its plain reference
    counts: str                # the module of its reckoning
    seed: int                  # the toy weights' seed
    toy: tuple = ()            # (key, value) over the rehearsal sizes
    buckets: tuple = (8, 16)   # a test replica's warm-up buckets
    trace_steps: int = 300     # the control's traced steps


FAMILIES = {
    "granite": Family("granite-4.0-h-micro.serve",
                      "granite4hm_serve_agent_sat", "granite_reference",
                      "granite_counts", 29, trace_steps=500),
    "solar": Family("Solar-Open2-250B.serve", "solaropen2_serve_reason_sat",
                    "solar_open2_reference", "solar_open2_counts", 34,
                    trace_steps=500),
    "mimo": Family("MiMo-V2.5.serve", "mimov25_serve_mixlen_sat",
                   "mimo_v2_reference", "mimo_v2_counts", 36,
                   buckets=(8, 16, 32)),
    "axk1": Family("A.X-K1.serve", "axk1_serve_longdoc_sat",
                   "axk1_reference", "axk1_counts", 40, buckets=(8, 16, 32)),
    "ouro": Family("Ouro-2.6B.serve", "ouro26b_serve_shortreason_sat",
                   "ouro_reference", "ouro_counts", 43,
                   toy=(("total_ut_steps", 3),), buckets=(8, 16, 32)),
}


def load(family: str) -> dict:
    """A family's configuration file, as the benchmark reads it."""
    return harness.load_json(harness.HERE, "configs",
                             FAMILIES[family].config + ".json")


def module(family: str, what: str = "reference"):
    """A family's reference (or ``counts``) module."""
    return importlib.import_module(getattr(FAMILIES[family], what))


def toy(family: str) -> dict:
    """The configuration's file at its rehearsal sizes, in float32 so
    that the program and the reference differ by rounding alone."""
    cfg = load(family)
    cfg = harness.merged(cfg, cfg["rehearsal"])
    cfg["model"] = {**cfg["model"], "args": {**cfg["model"]["args"], **F32}}
    cfg.update(FAMILIES[family].toy)
    return cfg


def seeded(family: str, cfg: dict, seed: int | None = None) -> dict:
    """The reference's weights of ``cfg``."""
    run = types.SimpleNamespace(
        config=cfg, resolve=harness.resolve,
        seed=FAMILIES[family].seed if seed is None else seed)
    return module(family).weights(run)


def seeded_layer(family: str, cfg: dict, **over) -> dict:
    """Layer 1 of the reference's weights of ``cfg`` with ``over``, the
    layers after it not seeded: a layer's weights depend on its index
    alone."""
    cut = {**cfg, "layer_types": cfg["layer_types"][:2], **over}
    return seeded(family, cut)["layer_1"]


def model_config(cfg: dict, **overrides) -> hybrid.HybridConfig:
    return hybrid.HybridConfig(**{**harness.build_args(cfg), **overrides})


def tokens_of(seed: int, *shape) -> jax.Array:
    return jax.random.randint(jax.random.key(seed), shape, 2, 256)


def reference_logits(ref, params, tokens, cfg):
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, t: ref.logits(p, t, cfg))(
            params, jnp.asarray(tokens))


def widest_gap(ref, params, cfg, prompt, served) -> float:
    """By how much a served token's logit lies below the reference's
    best at its position, at worst."""
    logits = reference_logits(ref, params, [prompt + served], cfg)[0]
    at = np.arange(len(prompt) - 1, len(prompt) + len(served) - 1)
    return float(jnp.max(jnp.max(logits[at], -1)
                         - logits[at, np.asarray(served)]))


def fixtures(family: str):
    """A test file's ``toy`` and ``params``, made once a module."""
    @pytest.fixture(scope="module", name="toy")
    def toy_fixture() -> dict:
        return toy(family)

    @pytest.fixture(scope="module", name="params")
    def params_fixture(toy) -> dict:
        return seeded(family, toy)

    return toy_fixture, params_fixture


@pytest.fixture
def solo_world():
    """A Horovod world of one, a test at a time (a test file imports
    the fixture by name)."""
    import horovod_tpu as hvd
    hvd.shutdown()
    for var in ("HOROVOD_RANK", "HOROVOD_SIZE"):
        os.environ.pop(var, None)
    hvd.init()
    yield hvd
    hvd.shutdown()


# ---------------------------------------------------------------- a replica
def executor(family: str, model_cfg, params=None, **kw):
    from horovod_tpu.serving import ReplicaExecutor, ServeConfig
    return ReplicaExecutor(ServeConfig(**{**dict(
        model_cfg=model_cfg, max_batch=3, token_budget=64, max_seq=64,
        slo_ms=60000.0, warmup_buckets=FAMILIES[family].buckets),
        **kw}), params=params)


def serve(ex, prompts, max_new) -> list[list[int]]:
    for prompt, new in zip(prompts, max_new):
        ex.stats["offered"] += 1
        assert ex.queue.submit(list(prompt), new) is not None
    ex.serve_loop(stop_when=lambda: True)
    assert ex.stats["served"] == len(prompts)
    return [ex.completed[rid]["generated"] for rid in sorted(ex.completed)]


# ------------------------------------------------------- the slot cache
def programs(cfg: dict, **overrides):
    """The toy's model built with ``overrides``, its prefill (the
    prompt's true length a traced argument, as the replica passes it)
    and its decode step, which also hands out the step's counters summed
    over the layers, jitted -> (family, model, prefill, decode)."""
    config = model_config(cfg, decode=True, **overrides)
    family = config.family
    model = family.build(config)

    def decode(p, c, t):
        sown = {"counters": {}}
        logits, c = family.decode_step(model, {"params": p}, c, t,
                                       sown=sown)
        return logits, c, jnp.concatenate([jnp.zeros((0,), jnp.int32),
                                           *kvcache.summed(
                                               sown["counters"],
                                               family.decode_counters)])
    return family, model, jax.jit(lambda p, t, n: family.prefill(
        model, {"params": p}, t, lengths=n)), jax.jit(decode)


def programs_fixture(**overrides):
    """A test file's ``programs``: ``programs(toy, **overrides)``, built
    once a module."""
    @pytest.fixture(scope="module", name="programs")
    def fixture(toy):
        return programs(toy, **overrides)
    return fixture


def prefill_then_decode(ref, cfg, params, built, n, atol, steps=6):
    """A prompt of ``n`` in a bucket of 16 with ``lengths = n``, then
    ``steps - 1`` decode steps in slot 2 of a four-slot cache (``built``,
    one of ``programs``): every row of logits against the reference's
    full pass over ``n + steps - 1`` tokens.  -> (the prefill's cache
    row, the tokens, each step's counters)"""
    family, model, prefill, decode = built
    tokens = tokens_of(100 + n, 1, n + steps)
    padded = jnp.full((1, 16), 7, jnp.int32).at[:, :n].set(tokens[:, :n])
    logits, row = prefill(params, padded, jnp.int32(n))
    rows, counts = [logits[0, n - 1]], []
    cache = jax.tree_util.tree_map(
        lambda big, small: big.at[2].set(small[0]),
        family.fresh_cache(model, params, 4), row)
    for at in range(n, n + steps - 1):
        fed = jnp.zeros((4, 1), jnp.int32).at[2, 0].set(tokens[0, at])
        logits, cache, counted = decode(params, cache, fed)
        rows.append(logits[2, 0])
        counts.append(counted)
    want = reference_logits(ref, params, tokens, cfg)[
        0, n - 1:n + steps - 1]
    np.testing.assert_allclose(jnp.stack(rows), want, atol=atol)
    return row, tokens, counts


def slot_programs_fixture():
    """A test file's ``slot_programs``: ``slot_programs(tag)`` is the toy's
    model in a ``DenseSlotCache`` of 3 rows of 128 positions, its prefill
    and decode step (``programs``), and the row of a 60 token stream,
    built once a module and ``tag``.  A test that plants what changes
    the traced program asks for its own ``tag``."""
    @pytest.fixture(scope="module", name="slot_programs")
    def fixture(toy, params):
        built: dict = {}

        def slot_programs(tag: str = ""):
            if tag not in built:
                family, model, prefill, step = programs(toy,
                                                        max_seq_len=128)
                cache = slotcache.DenseSlotCache(types.SimpleNamespace(
                    slots=3, max_seq=128, warmup_buckets=()), family,
                    model, {})
                _, stale = prefill(params, tokens_of(99, 1, 64),
                                   jnp.int32(60))
                built[tag] = (model, cache, prefill, step, stale)
            return built[tag]
        return slot_programs
    return fixture


def decode_through_the_slot_cache(ref, cfg, params, built, n, bucket,
                                  steps):
    """A prompt of ``n`` right-padded to ``bucket``, inserted as row 2 of
    a slot cache whose last occupant was another stream (``built``, one
    of ``slot_programs``), then ``steps`` tokens decoded there: every
    row of logits against the reference's full forward pass.  -> (the
    cache, the model, the tokens)"""
    model, cache, prefill, step, stale = built
    cache.fresh(params)
    cache.tree = cache._insert_jit(cache.tree, stale, np.int32(2))
    tokens = tokens_of(n, 1, n + steps)
    want = reference_logits(ref, params, tokens, cfg)[0]
    padded = jnp.ones((1, bucket), jnp.int32).at[:, :n].set(tokens[:, :n])
    logits, row = prefill(params, padded, jnp.int32(n))
    np.testing.assert_allclose(logits[0, n - 1], want[n - 1], atol=2e-5)
    cache.tree = cache._insert_jit(cache.tree, row, np.int32(2))
    for at in range(n, n + steps):
        fed = jnp.zeros((3, 1), jnp.int32).at[2, 0].set(tokens[0, at])
        logits, cache.tree, _ = step(params, cache.tree, fed)
        np.testing.assert_allclose(logits[2, 0], want[at], atol=2e-5)
    return cache, model, tokens


# ------------------------------------------- the benchmark's comparisons
def check_control(monkeypatch, capsys, family: str, seed: int) -> dict:
    """``--check control`` of the family's cell at its rehearsal sizes,
    64 requests compared: the check's line, and the exit code."""
    load_json = harness.load_json

    def patched(*parts):
        data = copy.deepcopy(load_json(*parts))
        for over in ({"served_check": {"requests": 64}},
                     {"trace_steps": FAMILIES[family].trace_steps}):
            if set(over) <= set(data):
                data["rehearsal"] = harness.merged(data["rehearsal"], over)
        return data

    monkeypatch.setattr(harness, "load_json", patched)
    code = harness.main(["--workload", FAMILIES[family].cell, "--seed",
                         str(seed), "--trace", "1", "--rehearse-cpu",
                         "--check", "control"])
    line, = [ln for ln in capsys.readouterr().out.splitlines()
             if " check {" in ln]
    return {"code": code, **harness.json.loads(line[line.index("{"):])}


def program_against_reference(ref, cfg: dict, params, keys) -> dict:
    """``served_gap`` on one stream of 21 prompt tokens (in the widest
    bucket, so right-padded) and 40 more: ``keys``, numbers that do not
    ask who chose the tokens."""
    tokens = np.zeros((1, 256), np.int32)
    tokens[0, :61] = np.asarray(tokens_of(5, 61))
    seen = ref.served_gap(cfg)(params, tokens, np.int32(21), np.int32(61))
    return {key: float(seen[key]) for key in keys}


def served_stream(cfg: dict, params, prompt: int = 21, new: int = 40):
    """What the program serves one stream, greedy: the prompt prefilled
    in a bucket of 32 (right-padded, its true length passed), then
    ``new`` tokens decoded one by one through the cache -> the tokens
    [1, 256] (prompt, served, padding) and where the served ones start
    and end."""
    _, _, prefill, step = programs(cfg, max_seq_len=128)
    tokens = np.zeros((1, 256), np.int32)
    tokens[0, :prompt] = np.asarray(tokens_of(5, prompt))
    logits, cache = prefill(params, jnp.asarray(tokens[:, :32]),
                            jnp.int32(prompt))
    token = jnp.argmax(logits[:, prompt - 1], -1)
    for at in range(prompt, prompt + new):
        tokens[0, at] = int(token[0])
        logits, cache, _ = step(params, cache, token[:, None])
        token = jnp.argmax(logits[:, 0], -1)
    return tokens, np.int32(prompt), np.int32(prompt + new)


def compared(ref, cfg: dict, params, keys) -> dict:
    """``served_gap`` on the stream the program served (``served_stream``):
    ``gap_mean`` and ``keys``."""
    tokens, first, length = served_stream(cfg, params)
    seen = ref.served_gap(cfg)(params, tokens, first, length)
    return {"gap_mean": float(seen["gap_sum"]) / int(length - first),
            **{key: float(seen[key]) for key in keys}}
