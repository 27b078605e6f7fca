"""hvd.decode_attend (ops/decode_attention.py): a decode step's attention
that reads each slot's keys and values up to its own live length.

- the kernel, interpreted, against ``kvcache.attend`` (the plain form) on
  ragged lengths, for the 7B head shape (32 heads of 128, group 1) and a
  grouped one (group 4 over 16 key-value heads of 128), bfloat16 leaves;
- positions past a slot's length hold NaN and must not reach the output;
- ``read_positions`` counts the blocks the kernel's work list visits;
- which leaves the kernel takes, and the block it reads them in;
- the lanes kernel at 8 key-value heads (ISSUE 37): Solar's cache (8
  heads of 128 under 64 query heads), MiMo's ring (keys 192, values
  128, a sink, one block a slot) and granite's cache (8 heads of 64
  under 32 query heads, two value heads a lane tile), the block the
  entry point really takes against ``read_positions``, and the custom
  call's two names; which serving cells' leaves the rule moved;
- the step's own row (ISSUE 39): the kernels take it beside the leaves,
  attend as if it were written and write it in place, to the bit what
  ``write_rows`` writes, on caches and on rings past their first lap,
  at a cache's last row, behind a float32 query and a sink; and which
  leaves ``kernel_writes`` says so for;
- the grid, a work list of live blocks: ``live_blocks`` itself,
  and the three kernels (the sublanes, the lanes, ``hvd.mla_decode``)
  over one batch of every edge against their plain forms.
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


def _bits(x) -> np.ndarray:
    """An array's bits: NaN equals NaN there."""
    x = np.asarray(x)
    return x.view(f"u{x.dtype.itemsize}")


def _step(leaves, lengths):
    """A decode step whose row is each slot's last live one: ``(new_k,
    new_v, lengths, at)`` as the kernels take them, the rows taken out
    of the clean ``leaves``."""
    at = jnp.asarray(lengths, jnp.int32) - 1
    return (*(jnp.take_along_axis(
        x, at.reshape(-1, *(1,) * (x.ndim - 1)), axis=1) for x in leaves),
        at + 1, at)


def _operands(heads, kv, lengths, qdtype=jnp.bfloat16):
    """Random q, leaves, the step (``_step``) and the clean leaves: the
    leaves handed to the kernel hold NaN at every dead position **and at
    the step's own row**, which only the step carries."""
    rng = np.random.default_rng(33)
    b = len(lengths)
    q = jnp.asarray(rng.standard_normal((b, 1, heads, D)), qdtype)
    keys, values = (jnp.asarray(rng.standard_normal((b, S, kv, D)),
                                jnp.bfloat16) for _ in range(2))
    step = _step((keys, values), lengths)
    dead = jnp.arange(S)[None, :, None, None] >= step[3][:, None, None, None]
    return q, jnp.where(dead, jnp.nan, keys), \
        jnp.where(dead, jnp.nan, values), step, (keys, values)


def _written(got, leaves, step):
    """``got``: what a kernel returned.  Its leaves are the handed ones
    with the step's row written, to the bit; its output comes back."""
    out, *new = got
    for mine, leaf, row in zip(new, leaves, step[:2]):
        np.testing.assert_array_equal(
            _bits(mine), _bits(da.write_rows(leaf, row, step[3])))
    return out


@pytest.mark.parametrize("lengths", sorted(LENGTHS))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_kernel_is_the_plain_form_up_to_each_slots_length(shape,
                                                              lengths):
    """Interpreted, in blocks of 16: every output is ``attend``'s to
    float32 rounding, and the NaN past each length reaches none."""
    heads, kv = SHAPES[shape]
    q, keys, values, step, clean = _operands(heads, kv, LENGTHS[lengths])
    lens = step[2]
    got = _written(da._decode_attend_pallas(
        q, keys, values, *step, 0.11, block=BLOCK, interpret=True),
        (keys, values), step)
    want = kvcache.attend(q, *clean, lens[:, None] - 1, 0.11)
    assert got.shape == want.shape == (len(lens), 1, heads, D)
    assert got.dtype == jnp.float32
    assert not np.isnan(np.asarray(got)).any()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-6, rtol=3e-6)


def test_a_float32_query_goes_through_in_three_exact_pieces():
    q, keys, values, step, clean = _operands(32, 32, (5, S), jnp.float32)
    got = _written(da._decode_attend_pallas(
        q, keys, values, *step, 0.09, block=BLOCK, interpret=True),
        (keys, values), step)
    want = kvcache.attend(q, *clean, step[3][:, None], 0.09)
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
    q, keys, values, step, clean = _operands(heads, kv, LENGTHS[lengths])
    lens = step[2]
    assert da.kernel_block(keys.shape, keys.dtype, interpret=True) == BLOCK
    assert da.kernel_block(keys.shape, keys.dtype) == 0       # a CPU
    want = kvcache.attend(q, *clean, lens[:, None] - 1, 0.11)
    calls = []
    real = da._decode_attend_pallas
    monkeypatch.setattr(da, "_decode_attend_pallas",
                        lambda *a, **kw: calls.append(kw) or real(*a, **kw))
    got = _written(da.decode_attend(q, keys, values, *step, 0.11,
                                    interpret=True), (keys, values), step)
    assert calls == [{"block": BLOCK, "interpret": True}]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-6, rtol=3e-6)
    # (the plain form reads the dead positions too: no NaN there, and
    # zeros where the step's row goes)
    blank = [da.write_rows(x, jnp.zeros_like(row), step[3])
             for x, row in zip(clean, step)]
    plain = _written(da.decode_attend(q, *blank, *step, 0.11), blank, step)
    assert len(calls) == 1
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(want))
    # An idle slot's cursor keeps counting: the row is the cache's last.
    idle = (*(x[:, -1:] for x in clean), jnp.full_like(lens, S + 8),
            jnp.full_like(lens, S + 7))
    over = _written(da.decode_attend(q, *clean, *idle, 0.11,
                                     interpret=True), clean, idle)
    full = kvcache.attend(q, *clean, jnp.full((len(lens), 1), S - 1), 0.11)
    np.testing.assert_allclose(np.asarray(over), np.asarray(full),
                               atol=3e-6, rtol=3e-6)


def _visited(lens, max_seq: int, block: int) -> list:
    """The ``(slot, block)`` of each step of the kernels' grid, in order:
    the first ``n`` items of ``live_blocks``."""
    n, slot, index, *_ = (np.asarray(x) for x in da.live_blocks(
        jnp.asarray(lens, jnp.int32), max_seq, block))
    return list(zip(slot[:n].tolist(), index[:n].tolist()))


@pytest.mark.parametrize("block", [8, 16, 64])
@pytest.mark.parametrize("lengths", sorted(LENGTHS))
def test_read_positions_counts_the_blocks_the_index_map_visits(lengths,
                                                               block):
    """Walking the kernel's grid, its work list, the blocks of a slot
    are the ones ``read_positions`` counts, each once: no grid step
    holds a dead block."""
    lens = np.asarray(LENGTHS[lengths], np.int32)
    steps = _visited(lens, S, block)
    visited = set(steps)
    assert len(visited) == len(steps)
    assert da.read_positions(lens, S, block) == len(visited) * block
    for slot, length in enumerate(lens):
        blocks = [at[1] for at in steps if at[0] == slot]
        assert blocks == list(range(-(-int(length) // block)))
    # With no kernel, a slot is read whole.
    assert da.read_positions(lens, S, 0) == len(lens) * S
    assert da.read_positions([], S, block) == 0


@pytest.mark.parametrize("shape, dtype, rule, block", [
    ((16, 4096, 32, 128), jnp.bfloat16, 128, 128),  # deepseek-llm-7b: 1 MB
    ((8, 8192, 16, 128), jnp.bfloat16, 256, 256),
    # Half a sublane tile of heads: they belong in the lanes (Solar, and
    # granite's of 64, two value heads a lane tile), and are read there
    # in the rule's block; a ring of MiMo's whole.
    ((32, 2560, 8, 64), jnp.bfloat16, 512, 0),
    ((32, 2560, 8 * 64), jnp.bfloat16, 512, 512),
    ((16, 4096, 8, 128), jnp.bfloat16, 512, 0),
    ((16, 4096, 8 * 128), jnp.bfloat16, 512, 512),
    ((64, 128, 8 * 192), jnp.bfloat16, 128, 128),
    ((16, 4096, 32, 128), jnp.float32, 0, 0),
    ((2, 64, 4, 16), jnp.bfloat16, 0, 0),           # the test-sized decoder
], ids=["lm7b", "kv16", "granite", "granite_lanes", "kv8", "kv8_lanes",
        "ring_lanes", "float32", "tiny"])
def test_the_block_follows_the_leaves_shape(shape, dtype, rule, block):
    """``rule``: ``block_positions`` for these heads; ``block``: what the
    compiled path reads a leaf of this very shape in."""
    _, max_seq, *heads = shape
    kv, d = heads if len(heads) == 2 else (1, *heads)
    assert da.block_positions(max_seq, kv, d, dtype) == rule
    assert da.kernel_block(shape, dtype, interpret=True) == block
    assert da.kernel_block(shape, dtype) == 0                 # a CPU
    if block:
        assert max_seq % block == 0
        assert block * kv * d * 2 <= da._BLOCK_BYTES


def test_a_narrow_head_keeps_the_plain_form():
    """The test-sized decoder's shape (4 query heads over 4 key-value
    heads of 16: rows of 64 lanes, neither a sublane tile nor whole
    lanes): ``decode_attend`` is ``attend``, interpreted or not."""
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((3, 1, 4, 16)), jnp.bfloat16)
    keys, values = (jnp.asarray(rng.standard_normal((3, 48, 4, 16)),
                                jnp.bfloat16) for _ in range(2))
    assert not da.lanes_layout(4, 16, 16, jnp.bfloat16)
    step = _step((keys, values), [1, 17, 48])
    want = kvcache.attend(q, keys, values, step[3][:, None], 1 / 16)
    for interpret in (False, True):
        assert not da.kernel_writes(keys.shape, keys.dtype,
                                    interpret=interpret)
        got = _written(da.decode_attend(q, keys, values, *step, 1 / 16,
                                        interpret=interpret),
                       (keys, values), step)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_the_kernel_carries_its_name_and_takes_the_leaves_as_they_lie():
    """One pallas_call named hvd.decode_attend whose key and value
    operands are the leaves themselves, four-dimensional, as the program
    was handed them (merged to [B, S, KV * D] they would be copied on
    the device every step), and come back as its second and third
    results, aliased: the step's row is written in place; the lengths,
    the rows' positions and the work list are its scalar prefetch, the
    list's count its grid's one, runtime, bound."""
    heads, kv = SHAPES["heads32_group1"]
    q, keys, values, step, _ = _operands(heads, kv, (1, S))
    jaxpr = jax.make_jaxpr(lambda *a: da.decode_attend(
        *a, 0.1, interpret=True))(q, keys, values, *step)
    # (the kernel's wrapper is jitted: one trace for all of a model's layers)
    inner, = [eqn for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == "jit"
              and eqn.params["name"] == "_decode_attend_pallas"]
    assert inner.invars[1:3] == jaxpr.jaxpr.invars[1:3]
    inner = inner.params["jaxpr"].jaxpr
    call, = [eqn for eqn in inner.eqns if eqn.primitive.name == "pallas_call"]
    assert call.params["name"] == "hvd.decode_attend"
    grid = call.params["grid_mapping"]
    assert grid.num_index_operands == 6 and grid.num_dynamic_grid_bounds == 1
    n, lengths, at, *work, _, new_k, new_v, k_in, v_in = call.invars
    assert n.aval.shape == () and n.aval.dtype == jnp.int32
    assert lengths.aval.shape == at.aval.shape == (2,)
    assert lengths.aval.dtype == at.aval.dtype == jnp.int32
    # one block a slot of 64 positions here: the list is as long
    assert [(x.aval.shape, x.aval.dtype) for x in work] \
        == [((2,), jnp.int32)] * 4
    assert new_k.aval.shape == new_v.aval.shape == (2, kv, D)
    assert k_in is inner.invars[1] and v_in is inner.invars[2]
    assert call.params["input_output_aliases"] == ((9, 1), (10, 2))
    assert [v.aval.shape for v in call.outvars[1:]] == [keys.shape] * 2
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda *a: da.decode_attend(*a, 0.1))(q, keys, values, *step))
    assert kvcache.attend is da.attend_plain


# --- 8 key-value heads in the lanes (ISSUE 37) ------------------------------
# Solar's attention layer: 64 query heads over 8 heads of 128; MiMo's
# ring: keys 192 and values 128 wide, a sink, the window one block;
# granite's attention layer: 32 query heads over 8 heads of 64, two
# value heads a lane tile.
KV8 = {"solar": dict(heads=64, kv=8, dk=128, dv=128, s=64, block=16,
                     sink=False, scope="hvd.decode_attend"),
       "ring": dict(heads=64, kv=8, dk=192, dv=128, s=16, block=16,
                    sink=True, scope="hvd.window_attend"),
       "granite": dict(heads=32, kv=8, dk=64, dv=64, s=64, block=16,
                       sink=False, scope="hvd.decode_attend")}
# 1, mid-block, a whole block, full (a ring at and under its window);
# 1, a block's edge, one past it, full
KV8_LENGTHS = {"solar": (1, 7, 16, 41, 64), "ring": (1, 7, 15, 16, 16),
               "granite": (1, 16, 17, 64)}


def _lanes_operands(shape, qdtype=jnp.bfloat16):
    """q, the leaves ``[B, S, KV * D]`` with NaN past each length **and
    at the step's own row**, the step (``_step``, its rows merged as the
    leaves are), the sink, and the clean leaves ``[B, S, KV, D]``."""
    spec = KV8[shape]
    lens = np.asarray(KV8_LENGTHS[shape], np.int32)
    rng = np.random.default_rng(37)
    b, s, kv = len(lens), spec["s"], spec["kv"]
    q = jnp.asarray(rng.standard_normal((b, 1, spec["heads"], spec["dk"])),
                    qdtype)
    keys = jnp.asarray(rng.standard_normal((b, s, kv, spec["dk"])),
                       jnp.bfloat16)
    values = jnp.asarray(rng.standard_normal((b, s, kv, spec["dv"])),
                         jnp.bfloat16)
    sink = jnp.asarray(rng.standard_normal(spec["heads"]), jnp.float32) \
        if spec["sink"] else None
    new_k, new_v, *where = _step((keys, values), lens)
    dead = np.arange(s)[None, :, None, None] >= lens[:, None, None, None] - 1
    merged = [jnp.where(dead, jnp.nan, x).reshape(b, s, -1)
              for x in (keys, values)]
    step = (new_k.reshape(b, 1, -1), new_v.reshape(b, 1, -1), *where)
    return q, merged, step, sink, (keys, values)


@pytest.mark.parametrize("qdtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16_q", "f32_q"])
@pytest.mark.parametrize("shape", sorted(KV8))
def test_the_lanes_kernel_serves_eight_heads(shape, qdtype):
    """Interpreted, against ``attend_plain`` over the same heads: every
    output to float32 rounding, the NaN past each length in none."""
    spec = KV8[shape]
    q, merged, step, sink, clean = _lanes_operands(shape, qdtype)
    lens = step[2]
    got = _written(da._decode_attend_lanes(
        q, *merged, *step, sink, 0.09, block=spec["block"], interpret=True),
        merged, step)
    want = da.attend_plain(q, *clean, lens[:, None] - 1, 0.09, sink)
    assert got.shape == want.shape == (len(lens), 1, spec["heads"],
                                       spec["dv"])
    assert got.dtype == jnp.float32
    assert not np.isnan(np.asarray(got)).any()
    np.testing.assert_allclose(got, want, atol=5e-6, rtol=5e-6)


@pytest.mark.parametrize("shape", sorted(KV8))
def test_read_positions_is_the_block_the_entry_point_takes(shape,
                                                           monkeypatch):
    """``decode_attend`` interpreted hands the lanes kernel the block
    ``kernel_block`` names for the very leaves (what
    ``serving/slotcache.py`` counts with), under the layer kind's name;
    walking the kernel's work list in that block visits the positions
    ``read_positions`` counts."""
    spec = KV8[shape]
    q, merged, step, sink, clean = _lanes_operands(shape)
    lens = step[2]
    assert da.lanes_layout(spec["kv"], spec["dk"], spec["dv"], jnp.bfloat16)
    monkeypatch.setattr(da, "_BLOCK_BYTES",
                        spec["block"] * spec["kv"] * spec["dk"] * 2)
    block = da.kernel_block(merged[0].shape, jnp.bfloat16, True,
                            merged[1].shape)
    assert block == spec["block"]
    assert da.kernel_block(clean[0].shape, jnp.bfloat16, True,
                           clean[1].shape) == 0   # not where they belong
    calls = []
    real = da._decode_attend_lanes
    monkeypatch.setattr(da, "_decode_attend_lanes",
                        lambda *a, **kw: calls.append(kw) or real(*a, **kw))
    got = _written(da.decode_attend(q, *merged, *step, 0.09, sink,
                                    interpret=True, scope=spec["scope"]),
                   merged, step)
    assert calls == [{"block": block, "interpret": True,
                      "name": spec["scope"]}]
    want = da.attend_plain(q, *clean, lens[:, None] - 1, 0.09, sink)
    np.testing.assert_allclose(got, want, atol=5e-6, rtol=5e-6)
    host = np.asarray(lens)
    visited = _visited(host, spec["s"], block)
    assert da.read_positions(host, spec["s"], block) == len(visited) * block
    # The plain form over the same leaves reads every slot whole.
    assert da.read_positions(host, spec["s"], 0) == len(host) * spec["s"]


def pallas_calls(jaxpr) -> list:
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(pallas_calls(sub))
    return found


@pytest.mark.parametrize("shape", sorted(KV8))
def test_a_cache_and_a_ring_carry_their_own_kernel_names(shape):
    """One pallas_call: ``hvd.decode_attend`` over a cache,
    ``hvd.window_attend`` over a ring (a device trace selects an
    operation by its name alone, and the two are counted apart); the
    leaves go in as they lie and come back aliased, the lengths, the
    rows' positions and the work list as the scalar prefetch."""
    spec = KV8[shape]
    q, merged, step, sink, _ = _lanes_operands(shape)
    jaxpr = jax.make_jaxpr(lambda q, k, v, *step: da.decode_attend(
        q, k, v, *step, 0.1, sink, interpret=True, scope=spec["scope"]))(
        q, *merged, *step)
    call, = pallas_calls(jaxpr.jaxpr)
    other = {"hvd.decode_attend", "hvd.window_attend"} - {spec["scope"]}
    named = str(call.params["name"]) \
        + str(call.params.get("name_and_src_info"))
    assert spec["scope"] in named and other.pop() not in named
    assert call.params["grid_mapping"].num_index_operands == 6
    assert [v.aval.shape for v in call.invars[-2:]] \
        == [v.aval.shape for v in call.outvars[1:]] \
        == [x.shape for x in merged]
    assert call.params["input_output_aliases"] == ((10, 1), (11, 2))


# --- the step's own row, written by the kernel (ISSUE 39) -------------------
# The shapes above and a ring as long as MiMo's: heads, key-value heads,
# key and value widths, positions, block; ``window``: the leaves are a
# ring; ``lanes``: the heads lie side by side.
WRITES = {
    **{name: dict(heads=heads, kv=kv, dk=D, dv=D, s=S, block=BLOCK,
                  sink=False, window=0, lanes=False)
       for name, (heads, kv) in SHAPES.items()},
    "solar": {**KV8["solar"], "window": 0, "lanes": True},
    "granite": {**KV8["granite"], "window": 0, "lanes": True},
    "ring": {**KV8["ring"], "window": 16, "lanes": True},
    "ring128": dict(heads=64, kv=8, dk=192, dv=128, s=128, block=128,
                    sink=True, window=128, lanes=True,
                    scope="hvd.window_attend"),
}
# Write cursors a slot: the file's lengths less one; a slot at its last
# row and two past it (an idle slot's cursor keeps counting: the clamp).
CURSORS = {**{name: tuple(n - 1 for n in lens)
              for name, lens in LENGTHS.items()},
           "last_row_and_past": (S - 1, S, S + 7)}


@pytest.mark.parametrize("qdtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16_q", "f32_q"])
@pytest.mark.parametrize("cursors", sorted(CURSORS))
@pytest.mark.parametrize("shape", sorted(WRITES))
def test_the_kernel_writes_the_steps_row_and_attends_over_it(
        shape, cursors, qdtype, monkeypatch):
    """Interpreted.  The leaves hold NaN where the step's row belongs
    and past every live position; the kernel is handed the row and
    where it goes.  Its output is that of ``write_rows``, then
    ``attend_plain``; the leaves it returns are ``write_rows``' to the
    bit, every other row as it was.  A cache takes cursor ``i`` at row
    ``min(i, S - 1)`` and reads ``i + 1`` positions; a ring scales the
    cursors to its span and sends slot ``n`` round ``n`` more laps: row
    ``i % window`` of ``min(i + 1, window)`` live ones, the first lap
    and past it side by side."""
    spec = WRITES[shape]
    s, kv, window = spec["s"], spec["kv"], spec["window"]
    idx = np.asarray(CURSORS[cursors], np.int32)
    if window:
        idx = idx * s // S + window * np.arange(len(idx), dtype=np.int32)
    b = len(idx)
    at = idx % window if window else idx
    lengths = np.minimum(idx + 1, window or s)
    rng = np.random.default_rng(39)
    q = jnp.asarray(rng.standard_normal((b, 1, spec["heads"], spec["dk"])),
                    qdtype)
    sink = jnp.asarray(rng.standard_normal(spec["heads"]), jnp.float32) \
        if spec["sink"] else None
    dead = np.arange(s)[None, :] >= lengths[:, None]
    dead |= np.arange(s)[None, :] == np.minimum(at, s - 1)[:, None]
    leaves, rows = [], []
    for wide in (spec["dk"], spec["dv"]):
        leaf = jnp.where(dead[:, :, None, None], jnp.nan, jnp.asarray(
            rng.standard_normal((b, s, kv, wide)), jnp.bfloat16))
        row = jnp.asarray(rng.standard_normal((b, 1, kv, wide)),
                          jnp.bfloat16)
        if spec["lanes"]:
            leaf, row = leaf.reshape(b, s, -1), row.reshape(b, 1, -1)
        leaves.append(leaf)
        rows.append(row)
    monkeypatch.setattr(da, "_BLOCK_BYTES",
                        spec["block"] * kv * spec["dk"] * 2)
    assert da.kernel_writes(leaves[0].shape, jnp.bfloat16, leaves[1].shape,
                            spec["sink"], interpret=True)
    got, *new = da.decode_attend(
        q, *leaves, *rows, jnp.asarray(idx + 1 if not window else lengths),
        jnp.asarray(at), 0.1, sink, interpret=True,
        scope=spec.get("scope", "hvd.decode_attend"))
    written = [da.write_rows(leaf, row, jnp.asarray(at))
               for leaf, row in zip(leaves, rows)]
    want = da.attend_plain(
        q, *(jnp.nan_to_num(x) for x in written),   # it reads the dead too
        jnp.asarray(lengths)[:, None] - 1, 0.1, sink)
    assert not np.isnan(np.asarray(got)).any()
    np.testing.assert_allclose(got, want, atol=5e-6, rtol=5e-6)
    for mine, leaf, theirs in zip(new, leaves, written):
        np.testing.assert_array_equal(_bits(mine), _bits(theirs))
        elsewhere = ~(np.arange(s)[None, :]
                      == np.minimum(at, s - 1)[:, None])
        np.testing.assert_array_equal(_bits(mine)[elsewhere],
                                      _bits(leaf)[elsewhere])
        assert not np.isnan(np.asarray(mine, np.float32)[~elsewhere]).any()


@pytest.mark.parametrize("keys, values, dtype, sink, on_tpu, want", [
    ((16, 4096, 32, 128), None, jnp.bfloat16, False, True, True),
    ((16, 4096, 32, 128), None, jnp.bfloat16, False, False, False),
    ((16, 4096, 32, 128), None, jnp.bfloat16, True, True, False),
    ((16, 4096, 32, 128), None, jnp.float32, False, True, False),
    ((32, 2560, 512), None, jnp.bfloat16, False, True, True),
    ((32, 2560, 512), None, jnp.bfloat16, False, False, False),
    ((32, 2560, 8, 64), None, jnp.bfloat16, False, True, False),
    ((80, 4608, 8, 128), None, jnp.bfloat16, False, True, False),
    ((80, 4608, 1024), None, jnp.bfloat16, False, True, True),
    ((64, 12288, 768), (64, 12288, 512), jnp.bfloat16, False, True, True),
    ((64, 128, 1536), (64, 128, 1024), jnp.bfloat16, True, True, True),
    ((64, 128, 1536), (64, 128, 1024), jnp.bfloat16, True, False, False),
], ids=["lm7b", "lm7b_off_the_tpu", "sublanes_with_a_sink", "float32",
        "granite", "granite_off_the_tpu", "granite_not_in_the_lanes",
        "kv8_not_in_the_lanes", "solar", "mimo_global",
        "mimo_ring_with_its_sink", "mimo_ring_off_the_tpu"])
def test_the_kernel_writes_wherever_a_kernel_attends(
        keys, values, dtype, sink, on_tpu, want, monkeypatch):
    """``kernel_writes`` is ``decode_attend``'s own choice of path: yes
    where ``kernel_block`` finds a block (granite's 8 heads of 64 among
    them, in the lanes), but for a sink on heads in the sublanes; no for
    float32 leaves, heads that belong in the lanes and do not lie there,
    and off the TPU."""
    monkeypatch.setattr(da, "_on_tpu", lambda: on_tpu)
    assert da.kernel_writes(keys, dtype, values, sink) is want
    block = da.kernel_block(keys, dtype, values=values)
    assert want == bool(block and not (sink and len(keys) == 4))
    # Interpreted, the backend does not matter (both shapes tried off
    # the TPU are the kernel's).
    assert da.kernel_writes(keys, dtype, values, sink, interpret=True) \
        is (want or not on_tpu)


# The six serving cells' attention leaves: key-value heads, key and value
# widths, slots, positions, a sink; and what the rule gave each before
# value heads narrower than a lane tile went into the lanes: whether they
# lie in the lanes, the block the compiled path reads them in, whether
# the kernel writes the step's row.  (A.X-K1's latent leaf is not asked.)
SERVING_LEAVES = {
    "lm7b": ((32, 128, 128, 16, 4096, False), (False, 128, True)),
    "granite": ((8, 64, 64, 32, 2560, False), (False, 0, False)),
    "solar": ((8, 128, 128, 80, 4608, False), (True, 512, True)),
    "mimo_global": ((4, 192, 128, 64, 12288, False), (True, 512, True)),
    "mimo_ring": ((8, 192, 128, 64, 128, True), (True, 128, True)),
    "ouro": ((16, 128, 128, 8, 640, False), (False, 128, True)),
}


@pytest.mark.parametrize("cell", sorted(SERVING_LEAVES))
def test_only_granites_leaves_move(cell, monkeypatch):
    """``lanes_layout``, ``kernel_block`` and ``kernel_writes`` on the
    TPU give every serving cell's leaves the answers they gave before,
    but granite's: its 8 value heads of 64 now lie in the lanes, ``[32,
    2560, 512]``, read in blocks of 512 by the kernel, which writes the
    step's row."""
    (kv, dk, dv, slots, max_seq, sink), before = SERVING_LEAVES[cell]
    monkeypatch.setattr(da, "_on_tpu", lambda: True)
    lanes = da.lanes_layout(kv, dk, dv, jnp.bfloat16)
    keys, values = ((slots, max_seq, kv * w) if lanes
                    else (slots, max_seq, kv, w) for w in (dk, dv))
    now = (lanes, da.kernel_block(keys, jnp.bfloat16, values=values),
           da.kernel_writes(keys, jnp.bfloat16, values, sink))
    assert now == (before if cell != "granite" else (True, 512, True))


# --- the grid, a work list of live blocks --------------------------------------
@pytest.mark.parametrize("lengths, max_seq, block", [
    ((1, 16, 17, 64), 64, 16),       # 1, a block, one past it, max_seq
    ((64, 1, 1, 33), 64, 16),        # full first, then slots of one
    ((1, 7, 16, 16), 16, 16),        # a ring of one block: the old grid
    ((5,), 64, 8),
    ((1, 1), 64, 64),
], ids=["edges", "full_then_ones", "ring", "one_slot", "one_block"])
def test_live_blocks_lists_every_live_block_once_in_order(lengths, max_seq,
                                                          block):
    """``live_blocks``: the count is ``sum(ceil(length / block))``; the
    items go slot after slot, a slot's blocks in order; the first and
    last flags mark each slot's ends (a slot of one position is one item,
    both); the arrays are as long as the static grid, and the items past
    the count repeat the last."""
    n, slot, index, first, last = (np.asarray(x) for x in da.live_blocks(
        jnp.asarray(lengths, jnp.int32), max_seq, block))
    blocks = [-(-length // block) for length in lengths]
    want = [(b, j) for b, k in enumerate(blocks) for j in range(k)]
    assert int(n) == len(want) == sum(blocks)
    assert n.dtype == slot.dtype == index.dtype == first.dtype \
        == last.dtype == np.int32
    for x in (slot, index, first, last):
        assert x.shape == (len(lengths) * max_seq // block,)
    assert list(zip(slot[:n].tolist(), index[:n].tolist())) == want
    assert first[:n].tolist() == [int(j == 0) for _, j in want]
    assert last[:n].tolist() == [int(j == blocks[b] - 1) for b, j in want]
    for b, length in enumerate(lengths):
        if length == 1:
            mine = np.flatnonzero(slot[:n] == b)
            assert len(mine) == 1 and first[mine[0]] == last[mine[0]] == 1
    for x in (slot, index, first, last):
        assert (x[n:] == x[n - 1]).all()
    if max_seq == block:
        assert int(n) == len(lengths)        # a ring: a slot, one step


# kernel -> its shapes: query heads, key-value heads, key and value widths
# (an MLA leaf: one row of ``width``, the first ``rank`` its value)
GRID_KERNELS = {
    "sublanes": dict(heads=64, kv=16, dk=D, dv=D),
    "lanes": dict(heads=16, kv=8, dk=192, dv=128, sink=True),
    "mla": dict(heads=16, width=128, rank=32),
}
# layout -> (positions a slot, lengths, rows): lengths 1, a block, one
# past it, max_seq, and a full slot whose row lands in its first block
# (a ring past its lap writes so) beside one whose row ends its last; a
# ring whose span is one block, past its first lap in its last slot.
GRID_LAYOUTS = {
    "cache": (S, (1, BLOCK, BLOCK + 1, S, S), (0, BLOCK - 1, BLOCK, S - 1, 3)),
    "ring": (BLOCK, (1, 7, BLOCK, BLOCK), (0, 6, BLOCK - 1, 2)),
}


@pytest.mark.parametrize("layout", sorted(GRID_LAYOUTS))
@pytest.mark.parametrize("kernel", sorted(GRID_KERNELS))
def test_the_work_list_kernels_are_the_plain_forms(kernel, layout):
    """Each of the three kernels, interpreted in blocks of 16, over one
    batch of every edge of the work list: the leaves hold NaN past each
    length and where the step's row goes.  The output is the plain
    form's over the leaves ``write_rows`` writes; the leaves that come
    back are those, to the bit: the row where it goes, nothing else
    changed."""
    from horovod_tpu.ops import mla

    spec = GRID_KERNELS[kernel]
    s, lengths, at = GRID_LAYOUTS[layout]
    b = len(lengths)
    lens, rows_at = (jnp.asarray(x, jnp.int32) for x in (lengths, at))
    rng = np.random.default_rng(42)
    dead = (np.arange(s)[None, :] >= np.asarray(lengths)[:, None]) \
        | (np.arange(s)[None, :] == np.asarray(at)[:, None])
    dtype = jnp.float32 if kernel == "mla" else jnp.bfloat16
    widths = [(spec["width"],)] if kernel == "mla" else \
        [(spec["kv"] * w,) if kernel == "lanes" else (spec["kv"], w)
         for w in (spec["dk"], spec["dv"])]
    leaves = [jnp.asarray(np.where(
        dead.reshape(b, s, *(1,) * len(w)), np.nan,
        rng.standard_normal((b, s, *w))), dtype) for w in widths]
    new = [jnp.asarray(rng.standard_normal((b, 1, *w)), dtype)
           for w in widths]
    written = [da.write_rows(leaf, row, rows_at)
               for leaf, row in zip(leaves, new)]
    clean = [jnp.nan_to_num(x) for x in written]  # the plain form reads all
    q = jnp.asarray(rng.standard_normal(
        (b, 1, spec["heads"], spec.get("dk", spec.get("width")))), dtype)
    if kernel == "mla":
        got, *back = mla._mla_pallas(q, *leaves, *new, lens, rows_at, 0.1,
                                     spec["rank"], block=BLOCK,
                                     interpret=True)
        want = mla.mla_plain(q, *clean, lens[:, None] - 1, 0.1, spec["rank"])
    elif kernel == "lanes":
        sink = jnp.asarray(rng.standard_normal(spec["heads"]), jnp.float32)
        got, *back = da._decode_attend_lanes(
            q, *leaves, *new, lens, rows_at, sink, 0.1, block=BLOCK,
            interpret=True)
        want = da.attend_plain(q, *clean, lens[:, None] - 1, 0.1, sink)
    else:
        got, *back = da._decode_attend_pallas(
            q, *leaves, *new, lens, rows_at, 0.1, block=BLOCK, interpret=True)
        want = da.attend_plain(q, *clean, lens[:, None] - 1, 0.1)
    assert not np.isnan(np.asarray(got)).any()
    np.testing.assert_allclose(got, want, atol=5e-6, rtol=5e-6)
    row = np.arange(s)[None, :] == np.asarray(at)[:, None]
    for mine, leaf, theirs in zip(back, leaves, written):
        np.testing.assert_array_equal(_bits(mine), _bits(theirs))
        np.testing.assert_array_equal(_bits(mine)[~row],
                                      _bits(leaf)[~row])
        assert not np.isnan(np.asarray(mine, np.float32)[row]).any()
