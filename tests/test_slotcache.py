"""The seam of ISSUE 32: one function attends over the serving cache
(models/kvcache.py) and one module knows each layout of it
(serving/slotcache.py).

- the shared attention against the formulas it replaced, written out
  plainly here (a loop over rows and heads, one softmax at a time);
- ``ReplicaExecutor`` holds a slot cache of one layout and nothing of the
  other, and reads ``cfg.paged`` in two places;
- either layout, driven through the interface alone, generates what
  ``tfm.prefill`` and ``tfm.decode_step`` generate.
"""
from __future__ import annotations

import dataclasses
import inspect
import math
import os
import types
from typing import Any

import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from horovod_tpu.models import kvcache
from horovod_tpu.models import transformer as tfm

B, T, D = 2, 5, 8
LENGTHS = (5, 3)                 # true prompt lengths of the two rows
BLOCK, POOL = 4, 6
TABLES = ((2, 0, 5), (1, 4, 3))  # M = 3 blocks a row: 12 positions
THETA = 10000.0


class _Layer(nn.Module):
    """An attention layer that is only its cache: ``through(self, q, k,
    v, ...)`` is the function under test."""
    through: Any

    @nn.compact
    def __call__(self, q, k, v, *extra):
        return self.through(self, q, k, v, *extra)


def _plain(q, keys, values, depth, scale):
    """One query position, written out: ``q`` [H, D] at position
    ``depth`` over ``keys`` / ``values`` [S, KV, D], of which positions
    0..depth are visible; query head h reads key-value head h // group."""
    heads, kv = q.shape[0], keys.shape[1]
    out = np.zeros(q.shape, np.float64)
    for h in range(heads):
        k = keys[:depth + 1, h // (heads // kv)].astype(np.float64)
        v = values[:depth + 1, h // (heads // kv)].astype(np.float64)
        scores = scale * (k @ q[h].astype(np.float64))
        weights = np.exp(scores - scores.max())
        out[h] = (weights / weights.sum()) @ v
    return out


def _inputs(heads, kv, dtype):
    rng = np.random.default_rng(32)

    def draw(t, n):
        return jnp.asarray(rng.standard_normal((B, t, n, D)), dtype)
    prompt = draw(T, heads), draw(T, kv), draw(T, kv)
    step = draw(1, heads), draw(1, kv), draw(1, kv)
    return prompt, step


CASES = {
    # multi-head, rotary positions, 1/sqrt(d): transformer.Attention
    "multihead_rotary": dict(heads=4, kv=4, scale=1 / math.sqrt(D),
                             rotary=True, paged=False),
    # grouped 4-to-1, no positions, a given scale: hybrid.GroupedAttention
    "grouped_4to1": dict(heads=8, kv=2, scale=0.37, rotary=False,
                         paged=False),
    # the paged gather: the same attention over a pool through tables
    "paged_gather": dict(heads=4, kv=4, scale=1 / math.sqrt(D),
                         rotary=True, paged=True),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_shared_attention_is_the_formulas_it_replaced(case, dtype):
    """A padded prompt of 5 positions (true lengths 5 and 3), then one
    decode step with each row at its own depth: the step's output is the
    plain softmax over that row's own keys, in the cache's dtype."""
    heads, kv, scale, rotary, paged = (CASES[case][key] for key in (
        "heads", "kv", "scale", "rotary", "paged"))
    rotate = (lambda x, pos: tfm.apply_rope(x, pos, THETA)) if rotary \
        else None
    (q0, k0, v0), (q1, k1, v1) = _inputs(heads, kv, dtype)
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    if paged:
        layer = _Layer(lambda m, q, k, v, cursors, n: kvcache.paged_attention(
            m, q, k, v, jnp.asarray(TABLES), cursors, n, pool_blocks=POOL,
            block_tokens=BLOCK, dtype=dtype, scale=scale, rotate=rotate))
        _, mut = layer.apply({}, q0, k0, v0, jnp.zeros((B,), jnp.int32),
                             lengths, mutable=["cache"])
        cache = mut["cache"]
        out, mut = layer.apply({"cache": cache}, q1, k1, v1, lengths, None,
                               mutable=["cache"])
    else:
        layer = _Layer(lambda m, q, k, v: kvcache.cached_attention(
            m, q, k, v, max_seq_len=12, dtype=dtype, scale=scale,
            rotate=rotate))
        _, mut = layer.apply({}, q0, k0, v0, mutable=["cache"])
        cache = kvcache._with_cache_index(mut["cache"], lengths)
        out, mut = layer.apply({"cache": cache}, q1, k1, v1,
                               mutable=["cache"])
    assert out.shape == (B, 1, heads, D) and out.dtype == jnp.float32

    for b, depth in enumerate(LENGTHS):
        # The row's keys and values as the cache holds them: the true
        # prompt, then this step's, rotated at their absolute positions
        # and rounded to the cache's dtype.
        keys = jnp.concatenate([k0[b, :depth], k1[b]])[None]
        values = jnp.concatenate([v0[b, :depth], v1[b]])
        query = q1[b][None]
        if rotary:
            keys = tfm.apply_rope(keys, jnp.arange(depth + 1), THETA)
            query = tfm.apply_rope(query, jnp.asarray([depth]), THETA)
        want = _plain(np.asarray(query[0, 0], np.float32),
                      np.asarray(keys[0], np.float32),
                      np.asarray(values, np.float32), depth, scale)
        np.testing.assert_allclose(np.asarray(out[b, 0]), want, atol=2e-5,
                                   rtol=2e-5)
    if paged:
        # Position p of row b lives at pool[TABLES[b][p // BLOCK], p % BLOCK].
        pool = np.asarray(mut["cache"]["key_pool"], np.float32)
        for b, depth in enumerate(LENGTHS):
            stepped = tfm.apply_rope(k1[b][None], jnp.asarray([depth]), THETA)
            np.testing.assert_array_equal(
                pool[TABLES[b][depth // BLOCK], depth % BLOCK],
                np.asarray(stepped[0, 0], np.float32))
    else:
        assert (np.asarray(mut["cache"]["cache_index"])
                == np.asarray(LENGTHS) + 1).all()


# --- the executor holds one layout ------------------------------------------
def _solo_world():
    import horovod_tpu as hvd
    hvd.shutdown()
    for var in ("HOROVOD_RANK", "HOROVOD_SIZE"):
        os.environ.pop(var, None)
    hvd.init()
    return hvd


def _serve_cfg(paged: bool):
    from horovod_tpu.serving import ServeConfig
    return ServeConfig.from_env(max_batch=2, token_budget=64, max_seq=64,
                                slo_ms=60000.0, block_tokens=8, paged=paged)


_PAGED_ONLY = ("pool", "_tables", "_cursors", "_sink", "_blocks",
               "_paged_jit", "_paged_prefill_jit", "_copy_block_jit")
_DENSE_ONLY = ("_decode_jit", "_prefill_jit", "_insert_jit")


def test_the_executor_holds_one_layout_and_asks_for_it_twice():
    from horovod_tpu.serving import ReplicaExecutor, slotcache

    hvd = _solo_world()
    try:
        for paged, kind, absent in (
                (False, slotcache.DenseSlotCache, _PAGED_ONLY),
                (True, slotcache.PagedSlotCache, _DENSE_ONLY)):
            ex = ReplicaExecutor(_serve_cfg(paged))
            try:
                assert type(ex.cache) is kind
                assert not any(hasattr(ex.cache, name) for name in absent)
                assert not any(hasattr(ex, name)
                               for name in _PAGED_ONLY + _DENSE_ONLY)
                assert (ex.kv_stats() is not None) == paged
                assert ex.batcher.block_capacity \
                    == (ex.cfg.resolved_pool_blocks if paged else 0)
            finally:
                ex.close()
    finally:
        hvd.shutdown()
    source = inspect.getsource(ReplicaExecutor)
    assert source.count("cfg.paged") == 2
    for method in (ReplicaExecutor.__init__,
                   ReplicaExecutor._configure_groups):
        assert inspect.getsource(method).count("cfg.paged") == 1
    for name in ("self.pool", "_tables", "_cursors", "_sink"):
        assert name not in source, name


# --- either layout through the interface alone ------------------------------
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_a_layout_driven_through_the_interface_alone_serves_the_model(paged):
    """Admit, three decode steps, release, admit again into the same
    slot (beside a second request that keeps decoding): every token is
    the one ``tfm.prefill`` and ``tfm.decode_step`` give the request on
    a cache of its own.  A step is enqueued before the one before it is
    fetched (ISSUE 35): a slot's input token is the host's after an
    admission and the last step's result, on the device, otherwise.  The
    second admission's prefill is enqueued behind a step in flight, which
    is fetched before its first token."""
    from horovod_tpu.serving import slotcache
    from horovod_tpu.serving.replica import _decode_model_cfg, _seeded_params

    cfg = _serve_cfg(paged)
    dense_cfg = _decode_model_cfg(cfg)
    dense = tfm.TransformerLM(dense_cfg)
    params = _seeded_params(dense, 0)
    if paged:
        model = tfm.TransformerLM(dataclasses.replace(
            dense_cfg, paged=True, kv_pool_blocks=cfg.resolved_pool_blocks,
            kv_block_tokens=cfg.block_tokens))
        cache = slotcache.PagedSlotCache(cfg, tfm.FAMILY, model,
                                         stats := {"prefill_skipped": 0})
    else:
        cache = slotcache.DenseSlotCache(cfg, tfm.FAMILY, dense, stats := {})
    slots = [None] * cfg.slots
    last = np.zeros(cfg.slots, np.int32)
    from_host = np.ones(cfg.slots, bool)
    got: dict[int, list] = {}
    flying: list = []                 # (result, the rids it decodes for)

    def admit(slot, rid, prompt, in_flight=lambda: None):
        first = cache.admit(params, slot, prompt, 8)
        in_flight()                   # fetched behind the prefill
        last[slot] = cache.first_token(first)
        from_host[slot] = True
        slots[slot] = types.SimpleNamespace(seq_len=len(prompt), rid=rid)
        got[rid] = [int(last[slot])]

    def fetch():
        result, rids = flying.pop()
        nxt = cache.fetch(result)
        for i, rid in rids:
            got[rid].append(int(nxt[i]))

    def step():
        """Enqueue the next step, then fetch the one before it."""
        active = [i for i, s in enumerate(slots) if s is not None]
        result = cache.decode(params, last, from_host, active, slots)
        from_host[:] = False
        last[:] = -1                  # the host's copy is not read again
        for i in active:
            slots[i].seq_len += 1
        if flying:
            fetch()
        flying.append((result, [(i, slots[i].rid) for i in active]))

    def reference(prompt, count):
        padded = np.zeros((1, slotcache.prompt_bucket(cfg, len(prompt))),
                          np.int32)
        padded[0, :len(prompt)] = prompt
        logits, own = tfm.prefill(dense, {"params": params},
                                  jnp.asarray(padded),
                                  lengths=jnp.int32(len(prompt)))
        out = [int(jnp.argmax(logits[0, len(prompt) - 1]))]
        while len(out) < count:
            logits, own = tfm.decode_step(
                dense, {"params": params}, own,
                jnp.asarray([[out[-1]]], jnp.int32))
            out.append(int(jnp.argmax(logits[0, -1])))
        return out

    prompts = {0: [5, 9, 200, 31, 77, 3, 18, 64, 120],
               1: [44, 45, 46], 2: [5, 9, 200, 31, 77, 3, 18, 64, 120, 7]}
    try:
        cache.fresh(params)
        cache.warm(params, last)
        assert stats["cache_aliased_bytes"] == stats["cache_bytes"] > 0
        if not paged:      # no kernel on a CPU: write_rows' loops write
            assert stats["attend_layers"] == dense_cfg.num_layers
            assert stats["attend_write_fused_layers"] == 0
        admit(0, 0, prompts[0])
        admit(1, 1, prompts[1])
        for _ in range(3):
            step()
        fetch()                        # request 0's last row
        cache.release(0)
        slots[0] = None
        step()                         # request 1 alone, in flight
        admit(0, 2, prompts[2], fetch)  # the same slot, a longer prompt
        for _ in range(3):
            step()
        fetch()
        for rid, prompt in prompts.items():
            assert got[rid] == reference(prompt, len(got[rid])), rid
        assert [len(got[rid]) for rid in (0, 1, 2)] == [4, 8, 4]
        if paged:
            cache.release(0)
            cache.release(1)
            assert cache.kv_stats()["active"] == 0        # nothing leaked
            assert cache.kv_stats()["prefix_hits"] > 0    # request 2's
    finally:
        cache.close()


# --- the decode kernels through the dense layout (ISSUEs 33 and 37) ----------
def _heads16():
    """A decoder wide enough for the sublane kernel: 16 heads of 128."""
    return tfm.TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=16, d_model=2048, d_ff=64,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)


def _kv8_ring():
    """The Solar cell's attention shape (8 key-value heads of 128, under
    16 query heads here) beside a window layer of 8 heads with a sink:
    both lie in the lanes, ``[slots, max_seq, 1024]`` and ``[slots,
    window, 1024]``."""
    from horovod_tpu.models import hybrid
    return hybrid.HybridConfig(
        vocab_size=64, d_model=64, d_ff=64, num_heads=16, num_kv_heads=8,
        attn_head_dim=128, attention_multiplier=0.09, window=16,
        window_sink=True, layer_types=("attention", "window"),
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)


# decoder -> (its configuration, key-value heads, layers a span)
DECODERS = {"heads16": (_heads16, 16, {64: 2}),
            "kv8_ring": (_kv8_ring, 8, {64: 1, 16: 1})}


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "kernel"])
@pytest.mark.parametrize("decoder", sorted(DECODERS))
def test_the_dense_layout_serves_the_same_tokens_through_the_kernel(
        decoder, kernel, monkeypatch):
    """A bfloat16 decoder wide enough for hvd.decode_attend through
    ``DenseSlotCache``: with the kernel interpreted (the entry point's
    platform check patched, a cache's block cut to 16 positions so that
    slots end in different blocks, a ring of 16 one block) every token
    is the one the family's own ``prefill`` and ``decode_step`` give
    with the plain form, past a wrap of the ring; ``stats`` counts the
    live positions and what the compiled path reads for them in the
    blocks it really takes, a layer's worth (the mean over the layers),
    the grid steps, one a live block, beside the static grid's (the
    plain form: one a slot of one),
    and the layers whose kernel writes the step's row itself (ISSUE 39:
    all of them with the kernel, none on the CPU path); a slot released
    and admitted again serves its new request's tokens; one decode
    program either way."""
    import functools

    from horovod_tpu.ops import decode_attention as da
    from horovod_tpu.serving import ServeConfig, slotcache
    from horovod_tpu.serving.replica import _decode_model_cfg, _seeded_params

    config, kv, spans = DECODERS[decoder]
    cfg = ServeConfig.from_env(
        max_batch=3, token_budget=64, max_seq=64, slo_ms=60000.0,
        paged=False, warmup_buckets=(8,), model_cfg=config())
    family = cfg.model_cfg.family
    model = family.build(_decode_model_cfg(cfg))
    params = _seeded_params(model, 0)
    prompts = {0: list(range(3, 20)), 1: [44, 45, 46], 2: [9] * 31}
    steps, block, layers = 4, 16, sum(spans.values())

    def reference(prompt, steps=steps):
        padded = np.zeros((1, slotcache.prompt_bucket(cfg, len(prompt))),
                          np.int32)
        padded[0, :len(prompt)] = prompt
        logits, own = family.prefill(model, {"params": params},
                                     jnp.asarray(padded),
                                     lengths=jnp.int32(len(prompt)))
        out = [int(jnp.argmax(logits[0, len(prompt) - 1]))]
        for _ in range(steps):
            logits, own = family.decode_step(
                model, {"params": params}, own,
                jnp.asarray([[out[-1]]], jnp.int32))
            out.append(int(jnp.argmax(logits[0, -1])))
        return out

    want = {rid: reference(prompt) for rid, prompt in prompts.items()}
    if kernel:
        monkeypatch.setattr(da, "_on_tpu", lambda: True)
        monkeypatch.setattr(da, "_BLOCK_BYTES", block * kv * 128 * 2)
        monkeypatch.setattr(kvcache, "decode_attend", functools.partial(
            da.decode_attend, interpret=True))
    cache = slotcache.DenseSlotCache(cfg, family, model, stats := {})
    slots = [None] * cfg.slots
    last = np.zeros(cfg.slots, np.int32)
    got = {}
    try:
        cache.fresh(params)
        cache.warm(params, last)
        assert stats["cache_aliased_bytes"] == stats["cache_bytes"] > 0
        assert sorted(cache._attend_kinds) == sorted(
            (n, span, block if kernel else 0) for span, n in spans.items())
        if decoder == "kv8_ring":
            attn = cache.tree["layer_0"]["attn"], cache.tree["layer_1"]["attn"]
            assert attn[0]["cached_key"].shape == (3, 64, 8 * 128)
            assert attn[1]["ring_value"].shape == (3, 16, 8 * 128)
            assert stats["window_bytes"] == 2 * 3 * 16 * 1024 * 2
        for rid, prompt in prompts.items():
            last[rid] = cache.first_token(
                cache.admit(params, rid, prompt, 8))
            slots[rid] = types.SimpleNamespace(seq_len=len(prompt))
            got[rid] = [int(last[rid])]
        live = read = grid = full = 0
        for step in range(steps):
            # The host's tokens first, then the last result's own.
            nxt = cache.fetch(cache.decode(
                params, last, np.full(cfg.slots, step == 0), [0, 1, 2],
                slots))
            within = [(n, min(slots[rid].seq_len + 1, span), span)
                      for rid in prompts for span, n in spans.items()]
            live += sum(n * held for n, held, _ in within) // layers
            read += sum(n * (-(-held // block) * block if kernel else span)
                        for n, held, span in within) // layers
            grid += sum(n * (-(-held // block) if kernel else 1)
                        for n, held, _ in within) // layers
            full += sum(n * (span // block if kernel else 1)
                        for n, _, span in within) // layers
            for rid in prompts:
                last[rid] = -1
                slots[rid].seq_len += 1
                got[rid].append(int(nxt[rid]))
        assert got == want
        assert stats["attend_live_positions"] == live
        assert stats["attend_read_positions"] == read
        assert read > live
        # Every attention layer is a kernel's here, or none is: the plain
        # form takes one step of one a slot.
        assert (stats["attend_grid_steps"], stats["attend_grid_full"]) \
            == (grid, full)
        assert 0 < grid < full if kernel else grid == full == steps * 3
        # 18..21, 4..7 and 32..35 positions are 2, 1 and 2 (then 3)
        # blocks of 16 a step, of the static grid's 4 a slot.
        assert not (kernel and decoder == "heads16") \
            or (grid, full) == (5 + 3 * 6, steps * 3 * 4)
        # 18..21 and 4..7 positions read 32 and 16 a step in blocks of
        # 16; 32 positions read 32, then 33..35 read 48.
        assert not (kernel and decoder == "heads16") \
            or read == steps * (32 + 16) + 32 + 3 * 48
        # ISSUE 39: where a kernel attends it writes the step's row
        # itself, in every layer here; the plain form's layers write it
        # in a loop over the slots.
        assert stats["attend_layers"] == layers
        assert stats["attend_write_fused_layers"] == sum(
            n for n, span, block in cache._attend_kinds if block) \
            == (layers if kernel else 0)
        # A slot released and taken again: its new occupant's rows land
        # on the last one's, the other two slots decoding beside it.
        cache.release(1)
        again = [7, 8, 9, 10, 11]
        last[1] = cache.first_token(cache.admit(params, 1, again, 8))
        slots[1].seq_len = len(again)
        after = [int(last[1])]
        for step in range(3):
            nxt = cache.fetch(cache.decode(
                params, last, np.arange(cfg.slots) == (1 if step == 0
                                                       else -1),
                [0, 1, 2], slots))
            for rid in prompts:
                last[rid] = -1
                slots[rid].seq_len += 1
                got[rid].append(int(nxt[rid]))
            after.append(got[1].pop())
        assert after == reference(again)[:4]
        assert got[0] == want[0] + reference(prompts[0], 7)[-3:]
        assert cache._decode_jit._cache_size() == 1
    finally:
        cache.close()
