"""hvd.decode_attend (ops/decode_attention.py): a decode step's attention
that reads each slot's keys and values up to its own live length.

- the kernel, interpreted, against ``kvcache.attend`` (the plain form) on
  ragged lengths, for the 7B head shape (32 heads of 128, group 1) and a
  grouped one (group 4 over 16 key-value heads of 128), bfloat16 leaves;
- positions past a slot's length hold NaN and must not reach the output;
- ``read_positions`` counts the blocks the kernel's index map visits;
- which leaves the kernel takes, and the block it reads them in.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import kvcache
from horovod_tpu.ops import decode_attention as da

S, BLOCK = 64, 16
SHAPES = {"heads32_group1": (32, 32), "heads64_group4": (64, 16)}
D = 128
# 1, a block's edge, one past it, max_seq; a slot of 1 beside one of max_seq
LENGTHS = {"one_edge_past_full": (1, BLOCK, BLOCK + 1, S),
           "one_beside_full": (1, S),
           "mid_blocks": (2 * BLOCK - 1, 2 * BLOCK, 3 * BLOCK + 5),
           "all_full": (S, S)}


def _operands(heads, kv, lengths, qdtype=jnp.bfloat16):
    """Random q, leaves and lengths; dead positions of the leaves NaN
    (the clean leaves last)."""
    rng = np.random.default_rng(33)
    b = len(lengths)
    q = jnp.asarray(rng.standard_normal((b, 1, heads, D)), qdtype)
    keys, values = (jnp.asarray(rng.standard_normal((b, S, kv, D)),
                                jnp.bfloat16) for _ in range(2))
    lengths = jnp.asarray(lengths, jnp.int32)
    dead = jnp.arange(S)[None, :, None, None] >= lengths[:, None, None, None]
    return q, jnp.where(dead, jnp.nan, keys), \
        jnp.where(dead, jnp.nan, values), lengths, (keys, values)


@pytest.mark.parametrize("lengths", sorted(LENGTHS))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_kernel_is_the_plain_form_up_to_each_slots_length(shape,
                                                              lengths):
    """Interpreted, in blocks of 16: every output is ``attend``'s to
    float32 rounding, and the NaN past each length reaches none."""
    heads, kv = SHAPES[shape]
    q, keys, values, lens, clean = _operands(heads, kv, LENGTHS[lengths])
    got = da._decode_attend_pallas(q, keys, values, lens, 0.11,
                                   block=BLOCK, interpret=True)
    want = kvcache.attend(q, *clean, lens[:, None] - 1, 0.11)
    assert got.shape == want.shape == (len(lens), 1, heads, D)
    assert got.dtype == jnp.float32
    assert not np.isnan(np.asarray(got)).any()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-6, rtol=3e-6)


def test_a_float32_query_goes_through_in_three_exact_pieces():
    q, keys, values, lens, clean = _operands(32, 32, (5, S), jnp.float32)
    got = da._decode_attend_pallas(q, keys, values, lens, 0.09,
                                   block=BLOCK, interpret=True)
    want = kvcache.attend(q, *clean, lens[:, None] - 1, 0.09)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-6, rtol=3e-6)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(64),
                    jnp.float32)
    pieces = da._pieces(x)
    assert [p.dtype for p in pieces] == [jnp.bfloat16] * 3
    assert (sum(p.astype(jnp.float32) for p in pieces) == x).all()
    assert len(da._pieces(x.astype(jnp.bfloat16))) == 1


@pytest.mark.parametrize("lengths", sorted(LENGTHS))
def test_the_entry_point_takes_the_kernel_where_it_finds_a_block(
        lengths, monkeypatch):
    """``decode_attend`` interpreted goes through the kernel in the block
    ``block_positions`` gives (cut to 16 positions here); off a TPU and
    not interpreted it is ``attend`` itself; lengths past the cache (an
    idle slot's cursor keeps counting) are clipped to it."""
    heads, kv = SHAPES["heads32_group1"]
    monkeypatch.setattr(da, "_BLOCK_BYTES", BLOCK * kv * D * 2)
    q, keys, values, lens, clean = _operands(heads, kv, LENGTHS[lengths])
    assert da.kernel_block(keys.shape, keys.dtype, interpret=True) == BLOCK
    assert da.kernel_block(keys.shape, keys.dtype) == 0       # a CPU
    want = kvcache.attend(q, *clean, lens[:, None] - 1, 0.11)
    calls = []
    real = da._decode_attend_pallas
    monkeypatch.setattr(da, "_decode_attend_pallas",
                        lambda *a, **kw: calls.append(kw) or real(*a, **kw))
    got = da.decode_attend(q, keys, values, lens, 0.11, interpret=True)
    assert calls == [{"block": BLOCK, "interpret": True}]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-6, rtol=3e-6)
    plain = da.decode_attend(q, *clean, lens, 0.11)
    assert len(calls) == 1
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(want))
    over = da.decode_attend(q, *clean, jnp.full_like(lens, S + 7), 0.11,
                            interpret=True)
    full = kvcache.attend(q, *clean, jnp.full((len(lens), 1), S - 1), 0.11)
    np.testing.assert_allclose(np.asarray(over), np.asarray(full),
                               atol=3e-6, rtol=3e-6)


@pytest.mark.parametrize("block", [8, 16, 64])
@pytest.mark.parametrize("lengths", sorted(LENGTHS))
def test_read_positions_counts_the_blocks_the_index_map_visits(lengths,
                                                               block):
    """Walking the kernel's grid through its index map, the distinct
    blocks of a slot are the ones ``read_positions`` counts: a dead
    block repeats the index before it and is not fetched."""
    lens = np.asarray(LENGTHS[lengths], np.int32)
    visited = {tuple(int(i) for i in da._live_block(slot, j, lens,
                                                    block=block))
               for slot in range(len(lens)) for j in range(S // block)}
    assert all(at[2:] == (0, 0) for at in visited)
    assert da.read_positions(lens, S, block) == len(visited) * block
    for slot, length in enumerate(lens):
        blocks = sorted(at[1] for at in visited if at[0] == slot)
        assert blocks == list(range(-(-int(length) // block)))
    # With no kernel, a slot is read whole.
    assert da.read_positions(lens, S, 0) == len(lens) * S
    assert da.read_positions([], S, block) == 0


@pytest.mark.parametrize("shape, dtype, block", [
    ((16, 4096, 32, 128), jnp.bfloat16, 128),   # deepseek-llm-7b: 1 MB
    ((8, 8192, 16, 128), jnp.bfloat16, 256),
    ((32, 2560, 8, 64), jnp.bfloat16, 0),       # granite: lies position-minor
    ((16, 4096, 8, 128), jnp.bfloat16, 0),      # half a sublane tile of heads
    ((16, 4096, 32, 128), jnp.float32, 0),
    ((2, 64, 4, 16), jnp.bfloat16, 0),          # the test-sized decoder
], ids=["lm7b", "kv16", "granite", "kv8", "float32", "tiny"])
def test_the_block_follows_the_leaves_shape(shape, dtype, block):
    assert da.block_positions(*shape[1:], dtype) == block
    assert da.kernel_block(shape, dtype, interpret=True) == block
    assert da.kernel_block(shape, dtype) == 0                 # a CPU
    if block:
        assert shape[1] % block == 0
        assert block * shape[2] * shape[3] * 2 <= da._BLOCK_BYTES


def test_a_narrow_head_keeps_the_plain_form():
    """The hybrid family's shape (32 query heads over 8 key-value heads
    of 64): ``decode_attend`` is ``attend``, interpreted or not."""
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((3, 1, 32, 64)), jnp.bfloat16)
    keys, values = (jnp.asarray(rng.standard_normal((3, 48, 8, 64)),
                                jnp.bfloat16) for _ in range(2))
    lens = jnp.asarray([1, 17, 48], jnp.int32)
    want = kvcache.attend(q, keys, values, lens[:, None] - 1, 1 / 64)
    for interpret in (False, True):
        got = da.decode_attend(q, keys, values, lens, 1 / 64,
                               interpret=interpret)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_the_kernel_carries_its_name_and_takes_the_leaves_as_they_lie():
    """One pallas_call named hvd.decode_attend whose key and value
    operands are the leaves themselves, four-dimensional, as the program
    was handed them (merged to [B, S, KV * D] they would be copied on
    the device every step); the lengths are its scalar prefetch."""
    heads, kv = SHAPES["heads32_group1"]
    q, keys, values, lens, _ = _operands(heads, kv, (1, S))
    jaxpr = jax.make_jaxpr(lambda *a: da.decode_attend(
        *a, 0.1, interpret=True))(q, keys, values, lens)
    # (the kernel's wrapper is jitted: one trace for all of a model's layers)
    inner, = [eqn for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == "jit"
              and eqn.params["name"] == "_decode_attend_pallas"]
    assert inner.invars[1:3] == jaxpr.jaxpr.invars[1:3]
    inner = inner.params["jaxpr"].jaxpr
    call, = [eqn for eqn in inner.eqns if eqn.primitive.name == "pallas_call"]
    assert call.params["name"] == "hvd.decode_attend"
    assert call.params["grid_mapping"].num_index_operands == 1
    lengths, _, k_in, v_in = call.invars
    assert lengths.aval.shape == (2,) and lengths.aval.dtype == jnp.int32
    assert k_in is inner.invars[1] and v_in is inner.invars[2]
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda *a: da.decode_attend(*a, 0.1))(q, keys, values, lens))
    assert kvcache.attend is da.attend_plain
