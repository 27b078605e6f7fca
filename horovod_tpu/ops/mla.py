"""Multi-head latent attention over the dense serving cache (ISSUE 40).

A latent layer (DeepSeek-V3's MLA, ``models/hybrid.py:LatentAttention``)
keeps one row a position, ``[c_kv | rope(k_pe) | 0]``: the normalised
latent of ``kv_lora_rank`` channels and the one rotary key of
``qk_rope_head_dim`` channels that every head shares (576 a position at
A.X-K1's widths, against 64 heads x 320 expanded), zeros to whole lanes
(``row_width``: 640).  Its two paths:

- **expanded** (a prompt, and the model without a cache): ``expand``
  gives every head its key ``[k_nope_h | rope(k_pe)]`` and value ``v_h``
  from the latent, ``[k_nope_h | v_h] = (W_kvb c_kv)_h``, and the prompt
  attends over them as any other layer's (``attend_blocked``; keys as
  wide as ``nope + rope``, values ``v_head_dim``, a key-value head a
  query head);
- **absorbed** (a decode step): ``W_UK_h`` (the ``k_nope`` rows of
  ``W_kvb``) moves onto the query, ``q~_h = W_UK_h^T q_nope_h``, so that
  ``q_nope_h . k_nope_hj = q~_h . c_kv_j``; every head then reads the
  same 576-wide row, its first ``kv_lora_rank`` channels also its value,
  and ``W_UV_h`` (the value rows) is applied to the weighted latent
  afterwards, ``o_h = W_UV_h (sum_j p_hj c_kv_j)``.  The same
  mathematics; 64 query heads over one key.

``mla_decode`` is the absorbed step.  On a TPU (or ``interpret=True``,
the unit tests) it is one Pallas kernel named ``hvd.mla_decode`` (a
device trace selects an operation by ``<opcode> <name>``), which reads
each slot's latent rows in blocks **up to the slot's own live length**
(its grid is ``ops/decode_attention.py``'s work list of live blocks,
built from the lengths: a block past a slot's length costs no grid
step), computes ``[H x W] . [W x block]`` scores and ``[H x block] .
[block x rank]`` outputs on the matrix unit with the online softmax
across blocks, and **writes the step's latent row itself** (ISSUE 39's
rule: it lays the row into the block that holds its position in VMEM,
before the scores, and writes back the aligned tile of 16 positions that
holds it; the leaf is aliased, so no serial loop over the slots writes
it).  Elsewhere ``write_rows``, then ``mla_plain``, the kernel's
reference, in plain ``jax.numpy``.  Both return the weighted latent in
float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .decode_attention import NEG_INF, _LANE, work_list_call, write_rows

KERNEL = "hvd.mla_decode"
# One block of latent rows in VMEM (double-buffered: two of them): 1,024
# positions of A.X-K1's 640 bfloat16 channels, so that a slot of 14,336
# positions is at most 14 grid steps (a grid step costs some 0.35 us on
# a v5e whatever it reads).
_BLOCK_BYTES = 2 << 20
_WRITE_TILE = 16     # positions of bfloat16 rows a packed tile holds


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def row_width(rank: int, rope: int) -> int:
    """Channels of a latent leaf's row: the latent and the rotary key,
    then zeros to whole lanes (576 -> 640).  A row of 576 lies
    position-minor on the chip, ``{1,2,0}``, where the layout need not
    pad it, and the compiler copies each leaf to and from the kernel's
    layout every step (1 GB a layer each way; the layout pads it to 640
    lanes all the same)."""
    return -(-(rank + rope) // _LANE) * _LANE


def latent_row(c_kv, k_pe, dtype):
    """A position's row of the leaf: ``c_kv`` [B, T, C] (normalised)
    beside ``k_pe`` [B, T, R] (rotated), zeros to ``row_width``."""
    rank, rope = c_kv.shape[-1], k_pe.shape[-1]
    pad = row_width(rank, rope) - rank - rope
    return jnp.concatenate([c_kv.astype(dtype), k_pe.astype(dtype),
                            jnp.zeros((*c_kv.shape[:-1], pad), dtype)], -1)


def expand(q_nope, q_pe, c_kv, k_pe, w_uk, w_uv):
    """The expanded form's operands: ``q_nope`` [B, T, H, N] and ``q_pe``
    [B, T, H, R] (rotated), ``c_kv`` [B, T, C] (normalised) and ``k_pe``
    [B, T, R] (rotated), ``w_uk`` [C, H, N] and ``w_uv`` [C, H, V] ->
    ``(q, k, v)``: ``[B, T, H, N + R]`` queries, as many keys (each
    head's own ``k_nope`` beside the one shared rotary key) and ``[B, T,
    H, V]`` values, in the activations' type."""
    dtype = q_nope.dtype
    k_nope = jnp.einsum("btc,chn->bthn", c_kv, w_uk.astype(dtype))
    v = jnp.einsum("btc,chv->bthv", c_kv, w_uv.astype(dtype))
    shared = jnp.broadcast_to(k_pe[:, :, None, :].astype(dtype),
                              (*k_nope.shape[:3], k_pe.shape[-1]))
    return (jnp.concatenate([q_nope, q_pe.astype(dtype)], -1),
            jnp.concatenate([k_nope, shared], -1), v)


def absorb(q_nope, q_pe, w_uk):
    """The absorbed query ``[q~ | rope(q_pe) | 0]`` [B, T, H, row_width]:
    ``q~_h = W_UK_h^T q_nope_h``, in the activations' type."""
    return latent_row(jnp.einsum("bthn,chn->bthc", q_nope,
                                 w_uk.astype(q_nope.dtype)), q_pe,
                      q_nope.dtype)


def emit(latent_out, w_uv, dtype):
    """Each head's value from its weighted latent: ``W_UV_h o~_h``,
    ``latent_out`` [B, T, H, C] float32 -> [B, T, H, V] float32."""
    return jnp.einsum("bthc,chv->bthv", latent_out.astype(dtype),
                      w_uv.astype(dtype),
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# The plain form
# ---------------------------------------------------------------------------
def mla_plain(q: jax.Array, latent: jax.Array, positions, scale: float,
              rank: int) -> jax.Array:
    """``q`` [B, T, H, W] at absolute ``positions`` [B|1, T] over the
    latent rows ``latent`` [B, S, W] (``row_width``) -> float32 [B, T, H,
    rank]: the softmax of ``scale * q . row`` over the positions up to
    each query's own, weighing the rows' first ``rank`` channels."""
    with jax.named_scope(KERNEL):
        rows = latent.astype(jnp.float32)
        scores = jnp.einsum("bthw,bsw->bths", q.astype(jnp.float32),
                            rows) * scale
        seen = jnp.arange(latent.shape[1])[None, None, :] \
            <= positions[:, :, None]                           # [B|1, T, S]
        weights = jax.nn.softmax(
            jnp.where(seen[:, :, None, :], scores, NEG_INF), axis=-1)
        return jnp.einsum("bths,bsc->bthc", weights, rows[..., :rank])


# ---------------------------------------------------------------------------
# What the kernel reads
# ---------------------------------------------------------------------------
def kernel_block(shape: tuple, dtype, interpret: bool = False) -> int:
    """The block the compiled decode path reads a latent leaf ``[B,
    max_seq, width]`` of ``dtype`` in: the largest power of two that
    divides ``max_seq`` and keeps a block within ``_BLOCK_BYTES`` (1,024
    of A.X-K1's 14,336 positions of 640 bfloat16 channels); 0 where it
    runs the plain form (every backend but the TPU).  The choice
    ``mla_decode`` makes, for whoever counts what it reads; where there
    is a block the kernel writes the step's row."""
    if not (_on_tpu() or interpret):
        return 0
    _, max_seq, width = shape
    row = width * jnp.dtype(dtype).itemsize
    fit = [n for n in (8 << i for i in range(max_seq.bit_length()))
           if max_seq % n == 0 and n * row <= _BLOCK_BYTES]
    return max(fit, default=0)


# ---------------------------------------------------------------------------
# The kernel
# ---------------------------------------------------------------------------
def _mla_kernel(item, q_ref, new_ref, c_ref, o_ref, co_ref, m_ref, l_ref,
                acc_ref, *, scale: float, block: int, tile: int, rank: int):
    """One slot, one live block of latent rows ``c_ref`` [1, block, W]: scores
    ``[H, block]`` (a query head a sublane, a position a lane) from one
    product of the queries with the rows, the online softmax across
    blocks, the weights against the rows' first ``rank`` channels.  The
    step's own row (``new_ref`` [1, 1, W]) belongs at position
    ``item.row``: the block that holds it, always a live one, takes
    it in VMEM before the scores, and its aligned ``tile`` of positions
    goes out through ``co_ref`` with the row in it."""
    from jax.experimental import pallas as pl

    j, length = item.block, item.length
    row = item.row - j * block                     # in this block, if 0..

    @pl.when(item.first)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((row >= 0) & (row < block))
    def _take():
        rows = pl.ds(pl.multiple_of(row // tile * tile, tile), tile)
        new = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0) == row % tile
        c_ref[0, rows] = jnp.where(new, new_ref[0], c_ref[0, rows])
        co_ref[0] = c_ref[0, rows]

    live = length - j * block
    c = c_ref[0]
    c = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
                  < live, c, jnp.zeros_like(c))        # 0 * NaN is NaN
    s = jax.lax.dot_general(q_ref[0], c, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
                  < live, s, NEG_INF)                     # [H, block]
    m_prev = m_ref[...]                                   # [H, 128]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, :1])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_cur
    acc_ref[...] = acc_ref[...] * alpha[:, :1] + jnp.dot(
        p.astype(c.dtype), c[:, :rank],
        preferred_element_type=jnp.float32)

    @pl.when(item.last)
    def _finalize():
        o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


# Jitted, so that the layers of a model, which call it with the same
# shapes, share one traced and one lowered kernel (ops/decode_attention.py
# says what lowering it again for every layer cost).
@functools.partial(jax.jit,
                   static_argnames=("scale", "rank", "block", "interpret"))
def _mla_pallas(q, latent, new_row, lengths, at, scale, rank, *, block: int,
                interpret: bool):
    from jax.experimental.pallas import tpu as pltpu

    b, _, h, width = q.shape
    s = latent.shape[1]
    tile = min(_WRITE_TILE, block)
    a_slot = lambda n, w: ((1, n, w),                        # noqa: E731
                           lambda slot, j, row: (slot, 0, 0))
    out, latent = work_list_call(
        functools.partial(_mla_kernel, scale=scale, block=block, tile=tile,
                          rank=rank),
        (q.reshape(b, h, width).astype(latent.dtype),
         new_row.astype(latent.dtype), latent),
        lengths=lengths, at=at, max_seq=s, block=block,
        in_specs=[a_slot(h, width), a_slot(1, width),
                  ((1, block, width), lambda slot, j, row: (slot, j, 0))],
        out_specs=[a_slot(h, rank),
                   ((1, tile, width),
                    lambda slot, j, row: (slot, row // tile, 0))],
        scratch_shapes=[pltpu.VMEM((h, _LANE), jnp.float32),
                        pltpu.VMEM((h, _LANE), jnp.float32),
                        pltpu.VMEM((h, rank), jnp.float32)],
        out_shape=[jax.ShapeDtypeStruct((b, h, rank), jnp.float32),
                   jax.ShapeDtypeStruct(latent.shape, latent.dtype)],
        aliases={2: 1},                              # the leaf, in place
        name=KERNEL, interpret=interpret)
    return out.reshape(b, 1, h, rank), latent


def mla_decode(q: jax.Array, latent: jax.Array, new_row: jax.Array,
               lengths: jax.Array, at: jax.Array, scale: float, rank: int, *,
               interpret: bool = False) -> tuple:
    """One absorbed decode step over the latent leaf ``latent`` [B, S,
    W]: the step's row ``new_row`` [B, 1, W] is written at position
    ``at`` [B] (clamped to the last, as ``dynamic_update_slice`` clamps)
    and ``q`` [B, 1, H, W] attends over the first ``lengths`` [B]
    positions, the new row among them -> ``(float32 [B, 1, H, rank],
    latent)``.  Where ``kernel_block`` finds a block (a TPU, or
    interpreted) one kernel does both and a donated leaf is updated in
    place; elsewhere ``write_rows``, then the plain form."""
    rows = latent.shape[1]
    lengths = jnp.clip(lengths.astype(jnp.int32), 1, rows)
    block = kernel_block(latent.shape, latent.dtype, interpret)
    if not block:
        latent = write_rows(latent, new_row.astype(latent.dtype), at)
        return mla_plain(q, latent, lengths[:, None] - 1, scale,
                         rank), latent
    at = jnp.clip(at.astype(jnp.int32), 0, rows - 1)
    with jax.named_scope(KERNEL):
        return _mla_pallas(q, latent, new_row, lengths, at, scale, rank,
                           block=block, interpret=interpret)

