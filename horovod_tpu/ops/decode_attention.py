"""Attention over the dense serving cache, in its forms.

``softmax(scale * q k^T + causal mask) v`` in float32, query head ``h``
reading key-value head ``h // (H // KV)``; values may be narrower than
keys; with a ``sink`` [H] a learned scalar a query head takes part in
the softmax as one more column that carries no value; with a ``window``
key ``j`` is visible at ``i`` when ``i - window < j <= i``:

- ``attend_plain``: any number of query positions, in plain
  ``jax.numpy``; it reads every cached position and masks the dead ones
  afterwards.  Prefill, the paged layout and every backend but the TPU
  run it, and it is the kernel's reference.
- ``decode_attend``: one query position a slot (a decode step), and
  the step's own key and value row, which it writes.  On a TPU it is
  one Pallas kernel, named ``hvd.decode_attend`` the way the flash
  kernels carry their names (a device trace selects an operation by
  ``<opcode> <name>`` only), which reads each slot's keys and values
  in blocks **up to that slot's own live length and no further**: its
  grid is a work list of the live blocks alone (``live_blocks``, a
  scalar prefetch built from the lengths, its count the grid's runtime
  bound), so a block past a slot's length costs no grid step at all.
  One compiled program serves every mix of lengths.
- ``attend_blocked``: a whole prompt over its own keys and values, in
  blocks of query positions with the online softmax across key blocks
  (plain ``jax.numpy`` under the scope ``hvd.prefill_attend``): a query
  block visits the key blocks up to its own, a window layer's only the
  last ``ceil(window / block) + 1`` of them, so a prefill holds ``block
  x block`` scores a head at a time and not ``T x max_seq``.

Two layouts of the leaves, by ``lanes_layout``.  Heads that fill tiles
of sublanes lie ``[B, S, KV, D]``: a tile of bfloat16 holds 16 rows, so
16 key-value heads or more (the 7B's 32).  Fewer than 16 would leave
part of every tile empty (or lie position-minor), so their leaves keep
the heads side by side in the lanes, ``[B, S, KV * D]`` keys and ``[B,
S, KV * Dv]`` values, nothing padded, where the rows come to whole
lanes and each value head is whole lanes or a part of one that divides
it (MiMo's 4 heads of 192 and 128, the 8 of its rings, Solar's 8 of
128, granite's 8 of 64): there the kernel multiplies every query, laid
at its own head's lanes of a zero row, with the whole key row (the
matrix unit loads the same key tiles either way), runs the softmax a
query head a sublane, and meets each head's values, a slice of lanes,
in one product a head.  A window layer's ring goes through the
same kernel under its own name, ``hvd.window_attend``: a device trace
tells the rings' calls from the caches' by name alone.

The first kernel takes the cache leaves as they lie, ``[B, S, KV, D]``:
merged to ``[B, S, KV * D]`` they would be copied on the device every step (the
tiles of the last two dimensions differ).  A block of positions is read
as ``[block * KV, D]``, a row a (position, head) pair, and stays bfloat16
up to the matrix unit.  Scores for all heads come from one product of
the queries with those rows, of which each query keeps the columns of
its own key-value head; the softmax runs with (position, head) along the
lanes; the weights, spread back over the query rows, meet the values in
one product the other way.  A float32 operand (the softmax weights, a
float32 query) goes through the matrix unit as three bfloat16 pieces
that sum to it exactly, the products accumulated in float32: nothing is
rounded that ``attend_plain`` does not round.  The online softmax across
blocks is ``ops/flash_attention.py``'s.

**Who writes a decode step's row** (ISSUE 39).  Written outside the
kernel, a row a slot at the slot's own depth (``write_rows``) compiles
to a ``while`` loop over the slots, one loop a leaf, 3 to 4 us an
iteration whatever the row's bytes: 23% of the MiMo cell's device step.
So where a kernel attends, the kernel writes: it takes the new row and
each slot's position as operands (``at``, a second scalar prefetch),
lays the row into the block that holds that position **in VMEM**, before
the scores (always a live block; nothing relies on a write to HBM being
seen by the same call's reads), and returns the leaves aliased to its
inputs, of which it writes back only the smallest aligned piece that
holds the row: the one position ``[1, 1, KV, D]`` where a position is a
major dimension, a tile of 16 positions ``[1, 16, KV * D]``, filled
from the block in VMEM, where the heads lie in the lanes.  The rule is
``kernel_writes``, the very condition under which ``decode_attend``
takes a kernel: elsewhere (every backend but the TPU, float32 leaves, a
sink on heads in the sublanes) the path is ``write_rows``, then
``attend_plain``.  A prefill writes with ``write_rows`` always: one row,
once a request.

**The grid.**  The three decode kernels, these two and
``ops/mla.py``'s, run through ``work_list_call``: one grid step a live
block, slot after slot, the blocks of a slot in order, so a kernel's
output block and its accumulators carry over from one step of a slot to
the next.  A kernel body takes the step's ``Item`` (its block, the
slot's length and row, whether it is the slot's first or last step) and
its block specs map ``(slot, block, row)`` to a block; the list, the
scalar prefetch and the index maps are the helper's alone.

``block_positions`` is the one rule for the block's length, and
``read_positions`` the count of what the compiled path reads for a set
of lengths: the serving replica's ``attend_read_positions`` counter is
the latter, so it cannot drift from the kernel.  ``interpret=True`` runs
the kernel interpreted (the unit tests).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30
_LANE = 128
# One key block in VMEM.  Keys and values, each double-buffered, are four
# of them.  Measured on a v5e at the 7B shape (16 slots of 4,096, live
# lengths 34 to 1,699): blocks of 128 and of 256 positions take the same
# time where the mean length is 585 (0.331 and 0.335 ms a layer), and the
# smaller block reads less of the cache past a short context (0.157
# against 0.184 ms where every slot holds one position); 512 is slower
# (0.405).
_BLOCK_BYTES = 1 << 20
_VMEM_BYTES = 32 << 20     # those, and the scores and weights of a block


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# The plain form
# ---------------------------------------------------------------------------
def _by_head(q, keys, values):
    """The leaves as ``[B, S, KV, D]`` and ``[B, S, KV, Dv]``, whichever
    way they lie (``lanes_layout``)."""
    if keys.ndim == 4:
        return keys, values
    b, s, width = keys.shape
    kv = width // q.shape[-1]
    return keys.reshape(b, s, kv, -1), values.reshape(b, s, kv, -1)


def attend_plain(q: jax.Array, keys: jax.Array, values: jax.Array,
                 positions, scale: float, sink=None, window: int = 0,
                 scope: str = "hvd.decode_attend") -> jax.Array:
    """``q`` [B, T, H, D] at absolute ``positions`` [B|1, T] over
    ``keys`` [B, S, KV, D] / ``values`` [B, S, KV, Dv] (a group of 1 is
    plain multi-head) -> float32 [B, T, H, Dv].  Key ``s`` is visible at
    position ``p`` when ``s <= p`` (and ``p - s < window``, where there
    is one): right-padded prefill garbage and unwritten positions sit
    past every live query.  ``sink`` [H]: one more column of the softmax
    a query head, with no value."""
    keys, values = _by_head(q, keys, values)
    b, t, h, d = q.shape
    kv = keys.shape[2]
    with jax.named_scope(scope):
        at = jnp.arange(keys.shape[1])[None, None, :]
        mask = at <= positions[:, :, None]                     # [B|1, T, S]
        if window:
            mask &= positions[:, :, None] - at < window
        qf = q.astype(jnp.float32).reshape(b, t, kv, h // kv, d)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qf,
                            keys.astype(jnp.float32)) * scale
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
        if sink is None:
            weights = jax.nn.softmax(scores, axis=-1)
        else:
            column = jnp.broadcast_to(
                sink.astype(jnp.float32).reshape(1, kv, h // kv, 1, 1),
                (*scores.shape[:-1], 1))
            weights = jax.nn.softmax(
                jnp.concatenate([scores, column], axis=-1),
                axis=-1)[..., :-1]
        out = jnp.einsum("bkgqs,bskd->bqkgd", weights,
                         values.astype(jnp.float32))
    return out.reshape(b, t, h, values.shape[-1])


# ---------------------------------------------------------------------------
# A whole prompt, in blocks
# ---------------------------------------------------------------------------
PREFILL_BLOCK = 512     # query and key positions of one block


def attend_blocked(q: jax.Array, k: jax.Array, v: jax.Array, scale: float,
                   *, window: int = 0, sink=None,
                   block: int = PREFILL_BLOCK) -> jax.Array:
    """``q`` [B, T, H, D] at positions 0 to T - 1 over this call's own
    ``k`` [B, T, KV, D] and ``v`` [B, T, KV, Dv] -> float32 [B, T, H,
    Dv], what ``attend_plain`` gives for them, holding one block of
    queries against one block of keys at a time."""
    b, t, h, d = q.shape
    kv, dv = k.shape[2], v.shape[-1]
    group = h // kv
    block = min(block, t)
    pad = -t % block
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for x in (q, k, v))
    blocks = (t + pad) // block
    behind = -(-window // block) if window else blocks
    q = q.reshape(b, blocks, block, kv, group, d)
    k = k.reshape(b, blocks, block, kv, d)
    v = v.reshape(b, blocks, block, kv, dv)
    if sink is None:
        first = jnp.full((b, kv, group, block), NEG_INF, jnp.float32)
    else:
        first = jnp.broadcast_to(sink.astype(jnp.float32).reshape(
            1, kv, group, 1), (b, kv, group, block))
    at = jnp.arange(block)

    def one(i):
        """Query block ``i`` against the key blocks it can see."""
        mine = jax.lax.dynamic_index_in_dim(q, i, 1, keepdims=False)
        q_at = i * block + at[:, None]

        def step(j, carry):
            m, total, acc = carry
            keys = jax.lax.dynamic_index_in_dim(k, j, 1, keepdims=False)
            vals = jax.lax.dynamic_index_in_dim(v, j, 1, keepdims=False)
            k_at = j * block + at[None, :]
            seen = k_at <= q_at
            if window:
                seen &= q_at - k_at < window
            s = jnp.einsum("bqkgd,bskd->bkgqs", mine, keys,
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(seen, s, NEG_INF)
            # A row with nothing visible yet weighs its block evenly;
            # its own block comes last and wipes that (alpha = 0).
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            acc = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p.astype(vals.dtype), vals,
                preferred_element_type=jnp.float32)
            return m_new, total * alpha + jnp.sum(p, axis=-1), acc

        _, total, acc = jax.lax.fori_loop(
            jnp.maximum(i - behind, 0), i + 1, step,
            (first, jnp.where(first > NEG_INF / 2, 1.0, 0.0),
             jnp.zeros((b, kv, group, block, dv), jnp.float32)))
        return jnp.transpose(acc / total[..., None], (0, 3, 1, 2, 4))

    with jax.named_scope("hvd.prefill_attend"):
        out = jax.lax.map(one, jnp.arange(blocks))   # [blocks, B, block, ...]
    return jnp.moveaxis(out, 0, 1).reshape(b, blocks * block, h, dv)[:, :t]


# ---------------------------------------------------------------------------
# What the kernel reads
# ---------------------------------------------------------------------------
def lanes_layout(kv_heads: int, head_dim: int, value_dim: int,
                 dtype) -> bool:
    """Whether leaves of these heads keep them side by side in the
    lanes, ``[B, S, KV * D]`` and ``[B, S, KV * Dv]``: bfloat16 heads
    too few to fill a sublane tile of 16 rows (``[B, S, KV, D]`` would
    be padded there, or lie position-minor and be copied every step)
    whose key and value rows come to whole lanes and whose value heads
    are each whole lanes or a part of one that divides it, the slices
    the kernel meets them in.  Solar's 8 heads of 128, the 8 of MiMo's
    rings and granite's 8 of 64 (two value heads to a lane tile) are
    such."""
    return jnp.dtype(dtype) == jnp.bfloat16 and kv_heads < 16 \
        and not (kv_heads * head_dim) % _LANE \
        and not (kv_heads * value_dim) % _LANE \
        and not (value_dim % _LANE and _LANE % value_dim)


def block_positions(max_seq: int, kv_heads: int, head_dim: int,
                    dtype, value_dim: int = 0) -> int:
    """How many positions one block of the kernel holds for leaves
    ``[B, max_seq, kv_heads, head_dim]`` of ``dtype``: the largest
    power of two that divides ``max_seq`` and keeps a key block within
    ``_BLOCK_BYTES`` (128 positions of the 7B shape, 512 of MiMo's four
    heads of 192, of Solar's eight of 128 and of granite's eight of 64,
    a ring's 128 whole).  0,
    and the plain form runs, where the kernel cannot
    take the leaves as they lie: it wants bfloat16 and either
    ``lanes_layout`` or, heads in the sublanes, values as wide as the
    keys, a head width of whole lanes (a narrower leaf lies
    position-minor on the device, ``{1,3,2,0}``, and would be copied
    every step) and key-value heads that fill whole sublane tiles and
    divide the lanes."""
    value_dim = value_dim or head_dim
    if jnp.dtype(dtype) != jnp.bfloat16:
        return 0
    if not lanes_layout(kv_heads, head_dim, value_dim, dtype) and (
            head_dim % _LANE or value_dim != head_dim or kv_heads % 16
            or _LANE % kv_heads):
        return 0
    row = kv_heads * head_dim * 2
    fit = [n for n in (8 << i for i in range(max_seq.bit_length()))
           if max_seq % n == 0 and n * row <= _BLOCK_BYTES]
    return max(fit, default=0)


def kernel_block(shape: tuple, dtype, interpret: bool = False,
                 values: tuple | None = None) -> int:
    """The block the compiled decode path reads key leaves of ``shape``
    (and value leaves of ``values``, where they differ) in, 0 where it
    runs the plain form: the choice ``decode_attend`` makes, for whoever
    counts what it reads.  A leaf with the heads in its lanes, ``[B, S,
    W]``, counts as one head of ``W``."""
    if not (_on_tpu() or interpret):
        return 0
    _, max_seq, *heads = shape
    kv, d = heads if len(heads) == 2 else (1, *heads)
    wide = (values or shape)[-1]
    if len(heads) == 2 and lanes_layout(kv, d, wide, dtype):
        return 0                   # such heads belong in the lanes
    return block_positions(max_seq, kv, d, dtype, wide)


def read_positions(lengths, max_seq: int, block: int) -> int:
    """Positions the decode path reads for slots of live ``lengths``:
    each length rounded up to the kernel's ``block``; with no kernel
    (``block`` 0), ``max_seq`` a slot."""
    lengths = np.asarray(lengths, np.int64)
    if not block:
        return int(lengths.size) * max_seq
    return int((-(-np.clip(lengths, 1, max_seq) // block)).sum()) * block


def kernel_writes(keys: tuple, dtype, values: tuple | None = None,
                  sink: bool = False, interpret: bool = False) -> bool:
    """Whether a decode step over key leaves of shape ``keys`` (value
    leaves of ``values``; a layer with a ``sink`` or none) goes through
    a kernel, which then writes the step's row itself: where
    ``kernel_block`` finds a block, but for heads in the sublanes with a
    sink, which that kernel does not take.  The one rule: ``decode_attend``
    follows it, and whoever counts the layers asks it."""
    return bool(kernel_block(keys, dtype, interpret, values)) \
        and not (sink and len(keys) == 4)


# A row a slot at the slot's own depth.  XLA compiles it to a ``while``
# loop over the slots, 3 to 4 us an iteration whatever a row's bytes: a
# prefill writes so (one row, once a request), and a decode step where
# no kernel writes for it.
write_rows = jax.vmap(lambda leaf, new, at: jax.lax.dynamic_update_slice(
    leaf, new, (at,) + (0,) * (leaf.ndim - 1)))


# ---------------------------------------------------------------------------
# The grid: a work list of live blocks
# ---------------------------------------------------------------------------
def live_blocks(lengths: jax.Array, max_seq: int, block: int) -> tuple:
    """The decode kernels' work list for slots of live ``lengths`` [B]
    (int32, each in 1..``max_seq``): every block of every slot up to its
    length, slot after slot, a slot's blocks in order.  ->
    ``(n, slot, index, first, last)``: ``n``, the count ``sum(ceil(
    lengths / block))`` and the grid's bound; int32 arrays as long as
    the static grid, ``B * max_seq // block``, of each item's slot, its
    block of the slot, and 1 where it is its slot's first / last block.
    Items from ``n`` on repeat the last; no grid step reads them.

    Compares and sums over ``[items, B]`` only: a gather by slot (and
    a cumulative sum) compiles to chains of scalar fusions on a v5e,
    0.73 ms a step over the MiMo cell's seven calls by a device trace."""
    b = lengths.shape[0]
    blocks = (lengths + block - 1) // block
    ends = jnp.sum(jnp.where(jnp.arange(b)[:, None] >= jnp.arange(b),
                             blocks, 0), axis=1)     # of the slots up to b
    n = ends[-1]
    items = jnp.minimum(jnp.arange(b * (max_seq // block), dtype=jnp.int32),
                        n - 1)
    past = items[:, None] >= ends                    # the slots before it
    slot = jnp.sum(past, axis=1, dtype=jnp.int32)
    index = items - jnp.sum(jnp.where(past, blocks, 0), axis=1)
    last = jnp.any(items[:, None] + 1 == ends, axis=1)
    return n, slot, index, (index == 0).astype(jnp.int32), \
        last.astype(jnp.int32)


class Item(NamedTuple):
    """What a kernel body knows of its grid step (scalars): ``block``,
    the block of positions it holds; the slot's ``length`` and the
    ``row`` its step's own row is written at; whether this is the slot's
    ``first`` or ``last`` block."""
    block: jax.Array
    length: jax.Array
    row: jax.Array
    first: jax.Array
    last: jax.Array


def work_list_call(kernel, operands: tuple, *, lengths, at, max_seq: int,
                   block: int, in_specs: list, out_specs: list,
                   scratch_shapes: list, out_shape: list, aliases: dict,
                   name: str, interpret: bool):
    """``kernel(item, *refs)`` run once a live block of ``live_blocks``,
    a one-dimensional grid of runtime bound.  A spec is ``(block shape,
    where)``, ``where(slot, block, row)`` the block's index;
    ``aliases``, operand -> result, count the ``operands`` alone.  The
    lengths, the rows and the list go in by scalar prefetch.  A slot's
    output blocks keep their index from its first step to its last, so
    they and the scratch carry over; the dimension is ``arbitrary`` (a
    v5e has one core)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, *items = live_blocks(lengths, max_seq, block)

    def body(len_ref, at_ref, slot_ref, index_ref, first_ref, last_ref,
             *refs):
        i = pl.program_id(0)
        slot = slot_ref[i]
        kernel(Item(index_ref[i], len_ref[slot], at_ref[slot],
                    first_ref[i] == 1, last_ref[i] == 1), *refs)

    def spec(shape, where):
        return pl.BlockSpec(shape, lambda i, lens, rows, slot, index, *_:
                            where(slot[i], index[i], rows[slot[i]]))

    prefetch = (lengths, at, *items)
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=(n,),
            in_specs=[spec(*s) for s in in_specs],
            out_specs=[spec(*s) for s in out_specs],
            scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        input_output_aliases={len(prefetch) + i: o
                              for i, o in aliases.items()},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name=name,
    )(*prefetch, *operands)


# ---------------------------------------------------------------------------
# The kernel
# ---------------------------------------------------------------------------
def _pieces(x: jax.Array) -> list:
    """``x`` as bfloat16 arrays that sum to it exactly: itself if it is
    bfloat16, else the three successive roundings of a float32."""
    if x.dtype == jnp.bfloat16:
        return [x]
    rest, out = x.astype(jnp.float32), []
    for _ in range(3):
        out.append(rest.astype(jnp.bfloat16))
        rest = rest - out[-1].astype(jnp.float32)
    return out


def _fold(x: jax.Array, rows: int) -> jax.Array:
    """The sum of ``x``'s consecutive groups of ``rows`` rows."""
    return sum(x[i:i + rows] for i in range(0, x.shape[0], rows))


def _attend_kernel(item, q_ref, nk_ref, nv_ref, k_ref, v_ref, o_ref, ko_ref,
                   vo_ref, qp_ref, m_ref, l_ref, acc_ref, *, scale: float,
                   block: int, kv: int, group: int):
    """One slot, one live block of positions.  A block of a leaf is read as
    ``[block * KV, D]``, a row a (position, key-value head) pair, which
    is how it lies; query rows are ordered (group member, key-value
    head).  Scores come out ``[H, block * KV]``, every query against
    every pair: a query's own are the columns of its key-value head
    (``mine`` below), and a sum down the rows leaves them ``[group, block
    * KV]`` with the lane ``position * KV + head``, the layout the
    softmax runs in.  The weights go back the same way: spread over the
    rows, masked by ``mine``, one product with the values.  The step's
    own row (``nk_ref``, ``nv_ref``: [1, KV, D]) belongs at position
    ``item.row``: the block that holds it, always a live one, takes
    it in VMEM before the scores (the leaf in HBM has it only once the
    call is over), and ``ko_ref`` / ``vo_ref``, that one position of
    the leaves, carry it out.  Written with few operations: the kernel
    is lowered in every process that serves, and its lowering is set-up
    time."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    j, length = item.block, item.length
    row = item.row - j * block                     # in this block, if 0..
    h = kv * group
    lanes = block * kv
    d = k_ref.shape[-1]
    # [H, 128] and [H, lanes]: whether a lane is of the key-value head
    # that a query row reads (lane % KV == row % KV; 128 % KV == 0).
    own = jax.lax.rem(jax.lax.broadcasted_iota(jnp.int32, (h, _LANE), 0),
                      kv) \
        == jax.lax.rem(jax.lax.broadcasted_iota(jnp.int32, (h, _LANE), 1),
                       kv)
    mine = jnp.tile(own, (1, lanes // _LANE))

    def spread(x):
        """``x`` [group, n], a row a group member, to the query rows."""
        return jnp.repeat(x, kv, axis=0) if group > 1 \
            else jnp.broadcast_to(x, (h, x.shape[1]))

    def a_head(x):
        """``x`` [group, 128], lane ``c`` a partial result of head ``c %
        KV``, to the column [H, 1] of each query row's own head."""
        return jnp.max(jnp.where(own, spread(x), NEG_INF), axis=1,
                       keepdims=True)

    def all_lanes(x, op):
        """``x`` [group, block * KV] reduced by ``op`` over positions:
        [group, 128], lane ``c`` holding the result of head ``c % KV``."""
        while x.shape[1] > _LANE:       # halves: few operations to lower
            half = x.shape[1] // 2
            x = op(x[:, :half], x[:, half:])
        shift = kv
        while shift < _LANE:
            x = op(x, pltpu.roll(x, shift, axis=1))
            shift *= 2
        return x

    @pl.when(item.first)
    def _init():
        qp_ref[...] = jnp.concatenate(_pieces(q_ref[0]), axis=0)
        ko_ref[0] = nk_ref[...]
        vo_ref[0] = nv_ref[...]
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((row >= 0) & (row < block))
    def _take():
        # The step's row, into the block as it lies in VMEM: an input
        # block's buffer is the kernel's to write (nothing copies it
        # back).  A select over the whole block reads the same time on
        # the chip (1.040 against 1.044 ms for the 7B cell's four
        # layers) and lowers more operations.
        k_ref[0, pl.ds(row, 1)] = nk_ref[...]
        v_ref[0, pl.ds(row, 1)] = nv_ref[...]

    # Pairs (position, head) of this block that are live (every step's
    # block is): all of them but in the slot's last block.  Masking
    # every block costs nothing the chip shows (0.326 against 0.332 ms
    # a layer for a second, unmasked copy of this body; a ``cond``
    # around the values' mask copies the block: 0.429), and lowers once.
    live = (length - j * block) * kv
    k = k_ref[0].reshape(lanes, d)
    v = v_ref[0].reshape(lanes, d)
    v = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (lanes, 1), 0)
                  < live, v, jnp.zeros_like(v))        # 0 * NaN is NaN
    s = jax.lax.dot_general(qp_ref[...], k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = jnp.where(mine, _fold(s, h), 0.0)                  # [H, lanes]
    s = jnp.sum(s.reshape(group, kv, lanes), axis=1) * scale
    s = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
                  < live, s, NEG_INF)
    m_prev = m_ref[...]                                  # [group, 128]
    m_cur = jnp.maximum(m_prev, all_lanes(s, jnp.maximum))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - jnp.tile(m_cur, (1, lanes // _LANE)))
    l_ref[...] = l_ref[...] * alpha + all_lanes(p, jnp.add)
    m_ref[...] = m_cur
    weights = jnp.where(mine, spread(p), 0.0)
    pv = jnp.dot(jnp.concatenate(_pieces(weights), axis=0), v,
                 preferred_element_type=jnp.float32)        # [3H, D]
    acc_ref[...] = acc_ref[...] * a_head(alpha) + _fold(pv, h)

    @pl.when(item.last)
    def _finalize():
        o_ref[0] = (acc_ref[...] / a_head(l_ref[...])).astype(o_ref.dtype)


# Jitted, so that the layers of a model, which call it with the same
# shapes, share one traced and one lowered kernel: lowering it again
# for every layer was 1 s of a 7B decode program's 1.4 s here, paid at
# each of warm-up's two lowerings, and 3.5 s of set-up on the chip.
@functools.partial(jax.jit, static_argnames=("scale", "block", "interpret"))
def _decode_attend_pallas(q, keys, values, new_k, new_v, lengths, at, scale,
                          *, block: int, interpret: bool):
    from jax.experimental.pallas import tpu as pltpu

    b, _, h, d = q.shape
    _, s, kv, _ = keys.shape
    group = h // kv
    pieces = 1 if q.dtype == jnp.bfloat16 else 3

    leaf = ((1, block, kv, d), lambda slot, j, row: (slot, j, 0, 0))
    head = ((1, h, d), lambda slot, j, row: (slot, 0, 0))
    new = ((1, kv, d), lambda slot, j, row: (slot, 0, 0))
    # A position is a major dimension here: the step's row is a block.
    written = ((1, 1, kv, d), lambda slot, j, row: (slot, row, 0, 0))
    # Query rows by (group member, key-value head): head kv * group + g.
    rows = q.reshape(b, kv, group, d).swapaxes(1, 2).reshape(b, h, d)
    out, keys, values = work_list_call(
        functools.partial(_attend_kernel, scale=scale, block=block, kv=kv,
                          group=group),
        (rows, new_k.reshape(b, kv, d), new_v.reshape(b, kv, d), keys,
         values),
        lengths=lengths, at=at, max_seq=s, block=block,
        in_specs=[head, new, new, leaf, leaf],
        out_specs=[head, written, written],
        scratch_shapes=[pltpu.VMEM((pieces * h, d), jnp.bfloat16),
                        pltpu.VMEM((group, _LANE), jnp.float32),
                        pltpu.VMEM((group, _LANE), jnp.float32),
                        pltpu.VMEM((h, d), jnp.float32)],
        out_shape=[jax.ShapeDtypeStruct((b, h, d), jnp.float32),
                   jax.ShapeDtypeStruct(keys.shape, keys.dtype),
                   jax.ShapeDtypeStruct(values.shape, values.dtype)],
        aliases={3: 1, 4: 2},                       # the leaves, in place
        name="hvd.decode_attend", interpret=interpret)
    return out.reshape(b, group, kv, d).swapaxes(1, 2).reshape(b, 1, h, d), \
        keys, values


def _lanes_kernel(item, q_ref, sink_ref, nk_ref, nv_ref, k_ref, v_ref, o_ref,
                  ko_ref, vo_ref, m_ref, l_ref, acc_ref, *, scale: float,
                  block: int, tile: int, kv: int, group: int):
    """One slot, one live block of positions of leaves with the heads in the
    lanes: ``k_ref`` [block, KV * D], ``v_ref`` [block, KV * Dv].  A
    query row holds its head's query at that head's lanes and zeros
    elsewhere, so one product with the key rows gives every head's
    scores, ``[H, block]``, a query head a sublane and a position a
    lane; the softmax runs there; each key-value head's weights meet
    its own lanes of the values.  The running maximum starts at the
    head's sink and the sum at 1 where there is one (its column has no
    value), at ``NEG_INF`` and 0 where not.  The step's own row
    (``nk_ref``, ``nv_ref``: [1, width]) belongs at position
    ``item.row``: the block that holds it, always a live one, takes
    it in VMEM before the scores (the leaf in HBM has it only once the
    call is over), and its aligned ``tile`` of positions goes out
    through ``ko_ref`` / ``vo_ref`` with the row in it."""
    from jax.experimental import pallas as pl

    j, length = item.block, item.length
    row = item.row - j * block                     # in this block, if 0..
    h = kv * group
    dv = v_ref.shape[-1] // kv

    @pl.when(item.first)
    def _init():
        m_ref[...] = sink_ref[...]
        l_ref[...] = jnp.where(sink_ref[...] > NEG_INF / 2, 1.0, 0.0)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((row >= 0) & (row < block))
    def _take():
        # The step's row, into its tile of the block in VMEM (an input
        # block's buffer is the kernel's to write), and the tile out.
        rows = pl.ds(pl.multiple_of(row // tile * tile, tile), tile)
        new = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0) \
            == row % tile
        for leaf, fresh, out in ((k_ref, nk_ref, ko_ref),
                                 (v_ref, nv_ref, vo_ref)):
            leaf[0, rows] = jnp.where(new, fresh[0], leaf[0, rows])
            out[0] = leaf[0, rows]

    live = length - j * block
    v = v_ref[0]
    v = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
                  < live, v, jnp.zeros_like(v))        # 0 * NaN is NaN
    s = jax.lax.dot_general(q_ref[0], k_ref[0],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = _fold(s, h) * scale                              # [H, block]
    s = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
                  < live, s, NEG_INF)
    m_prev = m_ref[...]                                  # [H, 128]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, :1])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_cur
    weights = _pieces(p)
    # A value head narrower than a lane tile (granite's 64) is a part of
    # one: on a v5e this body takes 0.062 ms a call at granite's shape,
    # and a variant that meets two heads in one whole tile 0.063.
    for head in range(kv):
        rows = slice(head * group, (head + 1) * group)
        pv = jnp.dot(
            jnp.concatenate([w[rows] for w in weights], axis=0),
            v[:, head * dv:(head + 1) * dv],
            preferred_element_type=jnp.float32)      # [3 group, Dv]
        acc_ref[rows] = acc_ref[rows] * alpha[rows, :1] \
            + _fold(pv, group)

    @pl.when(item.last)
    def _finalize():
        o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


# What the lanes kernel writes a slot and a leaf: the smallest aligned
# run of positions that holds the step's row, a tile of bfloat16 rows
# (Pallas writes an output block back whole, so it is filled from the
# block in VMEM: 49 KB of MiMo's 1,536 lanes, where the block is 786).
# What the write costs on the chip is not these bytes but the five more
# blocked operands' bookkeeping, 0.07 to 0.12 us a grid step on a v5e.
_WRITE_TILE = 16


@functools.partial(jax.jit,
                   static_argnames=("scale", "block", "interpret", "name"))
def _decode_attend_lanes(q, keys, values, new_k, new_v, lengths, at, sink,
                         scale, *, block: int, interpret: bool,
                         name: str = "hvd.decode_attend"):
    from jax.experimental.pallas import tpu as pltpu

    b, _, h, d = q.shape
    _, s, width = keys.shape
    kv = width // d
    group, dv = h // kv, values.shape[-1] // kv
    tile = min(_WRITE_TILE, block)
    # Each query at its own head's lanes of a row of KV * D, in exact
    # bfloat16 pieces, the pieces one under the other.
    own = jnp.arange(kv)[:, None, None, None] == jnp.arange(kv)[None, None, :,
                                                                None]
    rows = jnp.concatenate([
        jnp.where(own, piece.reshape(b, kv, group, 1, d), 0)
        .reshape(b, h, width) for piece in _pieces(q)], axis=1)
    start = jnp.full((h,), NEG_INF, jnp.float32) if sink is None \
        else sink.astype(jnp.float32)
    a_slot = lambda n, w: ((1, n, w),                        # noqa: E731
                           lambda slot, j, row: (slot, 0, 0))
    leaf = lambda w: ((1, block, w),                         # noqa: E731
                      lambda slot, j, row: (slot, j, 0))
    written = lambda w: ((1, tile, w),                       # noqa: E731
                         lambda slot, j, row: (slot, row // tile, 0))
    out, keys, values = work_list_call(
        functools.partial(_lanes_kernel, scale=scale, block=block, tile=tile,
                          kv=kv, group=group),
        (rows, jnp.broadcast_to(start[:, None], (h, _LANE)), new_k, new_v,
         keys, values),
        lengths=lengths, at=at, max_seq=s, block=block,
        in_specs=[a_slot(rows.shape[1], width),
                  ((h, _LANE), lambda slot, j, row: (0, 0)),
                  a_slot(1, width), a_slot(1, values.shape[-1]),
                  leaf(width), leaf(values.shape[-1])],
        out_specs=[a_slot(h, dv), written(width), written(values.shape[-1])],
        scratch_shapes=[pltpu.VMEM((h, _LANE), jnp.float32),
                        pltpu.VMEM((h, _LANE), jnp.float32),
                        pltpu.VMEM((h, dv), jnp.float32)],
        out_shape=[jax.ShapeDtypeStruct((b, h, dv), jnp.float32),
                   jax.ShapeDtypeStruct(keys.shape, keys.dtype),
                   jax.ShapeDtypeStruct(values.shape, values.dtype)],
        aliases={4: 1, 5: 2},                       # the leaves, in place
        name=name, interpret=interpret)
    return out.reshape(b, 1, h, dv), keys, values


def decode_attend(q: jax.Array, keys: jax.Array, values: jax.Array,
                  new_k: jax.Array, new_v: jax.Array, lengths: jax.Array,
                  at: jax.Array, scale: float, sink=None, *,
                  interpret: bool = False,
                  scope: str = "hvd.decode_attend") -> tuple:
    """One decode step over the leaves ``keys`` / ``values`` (``[B, S,
    KV, D]`` or, ``lanes_layout``, ``[B, S, KV * D]``): the step's row
    ``new_k`` / ``new_v`` (``[B, 1, ...]``, laid and typed as the
    leaves) is written at position ``at`` [B] (clamped to the last, as
    ``dynamic_update_slice`` clamps), and ``q`` [B, 1, H, D] attends
    over the first ``lengths`` [B] positions, the new row among them
    (``at < lengths``: a cache's ``lengths - 1``, anywhere in a ring
    past its first lap) -> ``(float32 [B, 1, H, Dv], keys, values)``.
    Where ``kernel_writes`` (a TPU or interpreted, leaves the kernel
    finds a block for), one kernel does both and a donated leaf is
    updated in place; elsewhere ``write_rows``, then the plain form.
    Either way under ``scope``, which is also the lanes kernel's name: a
    window layer's ring is ``hvd.window_attend``."""
    rows = keys.shape[1]
    lengths = jnp.clip(lengths.astype(jnp.int32), 1, rows)
    if not kernel_writes(keys.shape, keys.dtype, values.shape,
                         sink is not None, interpret):
        keys, values = write_rows(keys, new_k, at), \
            write_rows(values, new_v, at)
        return attend_plain(q, keys, values, lengths[:, None] - 1, scale,
                            sink, scope=scope), keys, values
    block = kernel_block(keys.shape, keys.dtype, interpret, values.shape)
    at = jnp.clip(at.astype(jnp.int32), 0, rows - 1)
    with jax.named_scope(scope):
        if keys.ndim == 3:
            return _decode_attend_lanes(q, keys, values, new_k, new_v,
                                        lengths, at, sink, scale,
                                        block=block, interpret=interpret,
                                        name=scope)
        return _decode_attend_pallas(q, keys, values, new_k, new_v, lengths,
                                     at, scale, block=block,
                                     interpret=interpret)
