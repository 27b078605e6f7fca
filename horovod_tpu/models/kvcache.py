"""The model's side of the serving cache: the leaves of the "cache"
collection an attention layer keeps, the one attention over them, and
the calls that run a ``decode=True`` model through them.

Two layouts, chosen by the replica (``serving/slotcache.py``):

- dense: ``cached_key`` / ``cached_value`` [B, max_seq_len, KV, D] and
  ``cache_index`` [B], each row's write cursor (``cached_attention``,
  ``prefill``, ``decode_step``); values may be narrower than keys; a
  window layer keeps rings of ``window`` rows instead, ``ring_key`` /
  ``ring_value``; fewer than 16 bfloat16 key-value heads whose rows
  come to whole lanes and whose value heads are whole lanes or divide
  them (granite's 8 of 64) lie side by side in the lanes, [B,
  max_seq_len, KV * D], caches and rings alike
  (``ops/decode_attention.py:lanes_layout``); a latent layer (ISSUE 40)
  keeps one leaf, ``latent`` [B, max_seq_len, W], a position's
  normalised latent beside its rotated shared key, zeros to whole lanes
  (576 of A.X-K1's 640), read by every head
  (``latent_attention``, ``ops/mla.py``); a looped stack (Ouro's) keeps
  a layer's leaves once a pass, those of the passes after the first
  under ``pass_<u>`` (``pass_scope``);
- paged: ``key_pool`` / ``value_pool`` [blocks + 1, block_tokens, KV, D]
  shared by every row and addressed through block tables, the last row
  a write sink for padded positions (``paged_attention``, ``paged_*``).

Both write this call's keys and values, then ``attend``, but for a
decode step on the dense layout (one query position a row): that goes
through ``decode_attend``, which is handed the step's row and where it
belongs and returns the leaves with it written.  On a TPU, where a
kernel takes the leaves (``ops/decode_attention.py:kernel_writes``: the
7B's, MiMo's caches and rings, Solar's, granite's, Ouro's), the kernel
reads each row's keys and values up to its own live length and writes
the new row in place itself; elsewhere ``write_rows`` writes it, a
serial loop over the rows, and the plain form attends.  A long prompt,
and any prompt of a window layer, attends over its own keys and values
in blocks (``attend_blocked``).  A latent layer's decode step takes the
absorbed form, ``hvd.mla_decode``, which writes the step's row the same
way; its prompt writes its rows and attends in blocks over its keys and
values expanded from the latent.
A family's attention layer (``transformer.Attention``,
``hybrid.GroupedAttention``, ``hybrid.LatentAttention``) brings its
projections, its positional encoding and its score scale, and writes no
cache code of its own.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from flax.core import unfreeze

# The one attention over the cache, in its plain form, as a decode
# step's kernel and in blocks over a whole prompt
# (ops/decode_attention.py).
from ..ops import decode_attention, mla
from ..ops.decode_attention import (attend_blocked, attend_plain as attend,
                                    decode_attend, write_rows)

# A prefill whose float32 scores over its row of the cache would pass
# this many bytes goes in blocks over its own keys instead (a prompt of
# 8,192 tokens in a cache of 12,288 positions, 64 heads: 25.8 GB); under
# it the prefill programs are the ones PRs up to 35 compiled.
PLAIN_PREFILL_BYTES = 1 << 30


PASS_SCOPE = "pass_"


def pass_scope(loop: int) -> str:
    """The scope under which a layer's leaves of pass ``loop`` of a looped
    stack lie (``loop`` from 0; the first pass keeps the layer's own)."""
    return f"{PASS_SCOPE}{loop + 1}"


def later_pass(path: tuple) -> bool:
    """Whether the cache leaf at ``path`` (its keys, outermost first)
    belongs to a pass of a looped stack after the first."""
    return any(key.startswith(PASS_SCOPE) for key in path)


def cached_attention(module, q: jax.Array, k: jax.Array, v: jax.Array, *,
                     max_seq_len: int, dtype, scale: float,
                     rotate=None, window: int = 0, sink=None,
                     lengths=None, loop: int = 0) -> jax.Array:
    """Incremental attention over ``module``'s dense cache: write this
    call's K/V at each row's own depth, attend over the cached prefix
    (a decode step: ``decode_attend`` does both, in one kernel where it
    has one).
    ``rotate(x, positions)`` is the family's positional encoding, if it
    has one; positions are absolute, so the math is the full forward's.
    ``v`` may be narrower than ``k``; ``sink`` [H] is a column of the
    softmax with no value (ops/decode_attention.py).

    With a ``window`` the leaves are rings, ``ring_key`` / ``ring_value``
    [B, window, KV, D] (or, ``lanes_layout``, [B, window, KV * D]):
    position ``p`` lives at row ``p mod window``
    (rotary positions are in the keys before they are written, so the
    ring's order does not matter).  A decode step writes row ``index mod
    window`` and attends over the ``min(index + 1, window)`` rows that
    are live; a prompt (a call that starts its rows: a ring takes a
    whole prompt or one token) attends over its own keys and values in
    blocks, then the ring takes the last ``window`` positions before
    ``lengths``, the true length of a right-padded row, never the
    padding.

    ``loop``: the pass of a looped stack (``HybridConfig.loops``) this
    call belongs to.  Every pass keeps leaves of its own, of the same
    names: the first the module's, pass ``u`` > 1 under ``pass_<u>``
    (``pass_scope``), so a slot cache holds them as further layers."""
    b, t, kv, d = k.shape
    h, dv = q.shape[2], v.shape[-1]
    rows = window or max_seq_len
    names = ("ring_key", "ring_value") if window \
        else ("cached_key", "cached_value")
    holder = module.scope.push(pass_scope(loop)) if loop else module
    starts = not holder.has_variable("cache", names[0])
    lanes = decode_attention.lanes_layout(kv, d, dv, dtype)

    def leaf(name, wide):
        shape = (b, rows, kv * wide) if lanes else (b, rows, kv, wide)
        return holder.variable("cache", name, jnp.zeros, shape, dtype)

    cached_k, cached_v = leaf(names[0], d), leaf(names[1], dv)
    index = holder.variable("cache", "cache_index",
                            lambda: jnp.zeros((b,), jnp.int32))
    idx = index.value                                       # [B]
    positions = idx[:, None] + jnp.arange(t)[None, :]       # [B, T]
    if rotate is not None:
        q, k = rotate(q, positions), rotate(k, positions)
    index.value = idx + t
    k, v = k.astype(dtype), v.astype(dtype)
    if lanes:
        k, v = k.reshape(b, t, kv * d), v.reshape(b, t, kv * dv)
    if t == 1:     # a decode step: each row up to its own length, no further
        out, cached_k.value, cached_v.value = decode_attend(
            q, cached_k.value, cached_v.value, k, v,
            jnp.minimum(idx + 1, window) if window else idx + 1,
            idx % window if window else idx, scale, sink,
            scope="hvd.window_attend" if window else "hvd.decode_attend")
        return out
    if window:
        if not starts:
            raise ValueError("a window layer's ring takes a whole prompt "
                             "or one token")
        # Ring row r: the last position before the true length that
        # lies at r, and nothing where the prompt is shorter than that.
        end = jnp.full((b,), t, jnp.int32) if lengths is None \
            else jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,))
        last = end[:, None] - 1
        held = last - (last - jnp.arange(window)[None, :]) % window
        ax = (..., *(None,) * (k.ndim - 2))     # over a row, however it lies
        for cache, new in ((cached_k, k), (cached_v, v)):
            cache.value = jnp.where(
                (held >= 0)[ax], jnp.take_along_axis(
                    new, jnp.clip(held, 0, t - 1)[ax], axis=1),
                jnp.zeros((), dtype))
    else:
        cached_k.value = write_rows(cached_k.value, k, idx)
        cached_v.value = write_rows(cached_v.value, v, idx)
        if not (starts and 4 * h * t * max_seq_len > PLAIN_PREFILL_BYTES):
            return attend(q, cached_k.value, cached_v.value, positions,
                          scale, sink)
    if lanes:
        k, v = k.reshape(b, t, kv, d), v.reshape(b, t, kv, dv)
    return attend_blocked(q, k, v, scale, window=window, sink=sink)


def latent_attention(module, q_nope: jax.Array, q_pe: jax.Array,
                     c_kv: jax.Array, k_pe: jax.Array, w_uk: jax.Array,
                     w_uv: jax.Array, *, max_seq_len: int, dtype,
                     scale: float, rotate) -> jax.Array:
    """A latent layer's attention over ``module``'s dense cache (ISSUE
    40; ``ops/mla.py`` has the two forms): the leaf ``latent`` [B,
    max_seq_len, W] keeps a position's normalised latent ``c_kv`` [B, T,
    C] beside its rotated shared key ``k_pe`` [B, T, R], zeros to whole
    lanes (``mla.row_width``), and ``cache_index`` [B] each row's write
    cursor.  A decode step (one
    token a row) takes the absorbed form, ``mla_decode``, which writes
    the step's row; a prompt (a call that starts its rows) writes its
    rows and attends in blocks over its own keys and values, expanded by
    ``w_uk`` [C, H, N] and ``w_uv`` [C, H, V].  ``q_nope`` [B, T, H, N],
    ``q_pe`` [B, T, H, R]; ``rotate(x, positions)`` the layer's positional
    encoding -> float32 [B, T, H, V], each head's output before ``wo``.
    A padded prompt needs no lengths: the write cursor rewinds past its
    tail (``prefill``) and the decode steps overwrite it."""
    b, t, rank = c_kv.shape
    starts = not module.has_variable("cache", "latent")
    latent = module.variable(
        "cache", "latent", jnp.zeros,
        (b, max_seq_len, mla.row_width(rank, k_pe.shape[-1])), dtype)
    index = module.variable("cache", "cache_index",
                            lambda: jnp.zeros((b,), jnp.int32))
    idx = index.value                                       # [B]
    positions = idx[:, None] + jnp.arange(t)[None, :]       # [B, T]
    q_pe = rotate(q_pe, positions)
    k_pe = rotate(k_pe[:, :, None, :], positions)[:, :, 0]
    index.value = idx + t
    row = mla.latent_row(c_kv, k_pe, dtype)
    if t == 1:     # a decode step: each row up to its own length, no further
        out, latent.value = mla.mla_decode(
            mla.absorb(q_nope, q_pe, w_uk).astype(dtype), latent.value, row,
            idx + 1, idx, scale, rank)
        return mla.emit(out, w_uv, dtype)
    if not starts:
        raise ValueError("a latent layer's cache takes a whole prompt or "
                         "one token")
    latent.value = write_rows(latent.value, row, idx)
    with jax.named_scope("hvd.mla_expand"):
        q, k, v = mla.expand(q_nope, q_pe, c_kv, k_pe, w_uk, w_uv)
    return attend_blocked(q, k, v, scale)


def paged_attention(module, q: jax.Array, k: jax.Array, v: jax.Array,
                    block_tables, cursors, lengths, *, pool_blocks: int,
                    block_tokens: int, dtype, scale: float,
                    rotate=None) -> jax.Array:
    """Incremental attention over the shared block pool (ISSUE 14):
    this call's K/V scatter into pool rows addressed through each row's
    block table, then the table gathers the sequence back as [B, M*bt,
    KV, D] (logical position p of row b lives at pool[tables[b, p//bt],
    p%bt]) for the dense layout's ``attend``.  ``lengths`` masks a
    right-padded prefill: padded positions write to the pool's sink row,
    never a real block, and their logits are garbage the caller ignores."""
    b, t, kv, d = k.shape
    bt, sink = block_tokens, pool_blocks         # the sink: the last row
    key_pool = module.variable("cache", "key_pool", jnp.zeros,
                               (sink + 1, bt, kv, d), dtype)
    value_pool = module.variable("cache", "value_pool", jnp.zeros,
                                 (sink + 1, bt, kv, d), dtype)
    tables = jnp.asarray(block_tables, jnp.int32)          # [B, M]
    cursors = jnp.asarray(cursors, jnp.int32)              # [B]
    m = tables.shape[1]
    if lengths is None:
        valid = jnp.ones((b, t), bool)
    else:
        valid = jnp.arange(t)[None, :] \
            < jnp.asarray(lengths, jnp.int32)[:, None]
    positions = cursors[:, None] + jnp.arange(t)[None, :]   # [B, T]
    if rotate is not None:
        q, k = rotate(q, positions), rotate(k, positions)
    logical = jnp.minimum(positions // bt, m - 1)
    phys = jnp.take_along_axis(tables, logical, axis=1)     # [B, T]
    phys = jnp.where(valid, phys, sink).reshape(-1)
    offs = (positions % bt).reshape(-1)
    kp = key_pool.value.at[phys, offs].set(
        k.astype(dtype).reshape(b * t, kv, d))
    vp = value_pool.value.at[phys, offs].set(
        v.astype(dtype).reshape(b * t, kv, d))
    key_pool.value, value_pool.value = kp, vp
    # Gather each row's sequence back in logical order; positions past
    # the cursor (stale or sink-backed) are masked by ``attend``.
    k_seq = jnp.take(kp, tables, axis=0).reshape(b, m * bt, kv, d)
    v_seq = jnp.take(vp, tables, axis=0).reshape(b, m * bt, kv, d)
    return attend(q, k_seq, v_seq, positions, scale)


# -- a decode=True model through its cache: models/family.py's entry points
def _with_cache_index(cache: dict, lengths) -> dict:
    """``cache`` with every layer's write cursor set to ``lengths``
    (scalar or [B] int32): prefill() rewinds past padding with it."""
    lengths = jnp.asarray(lengths, jnp.int32)

    def fix(node):
        if not isinstance(node, dict):
            return node
        return {key: (jnp.broadcast_to(lengths, val.shape).astype(val.dtype)
                      if key == "cache_index" else fix(val))
                for key, val in node.items()}
    return fix(unfreeze(cache))


def _apply(model, variables: dict, tokens, sown, **kw):
    """``model.apply`` through the cache -> ``(logits, cache)``.
    ``sown``, where a caller gives one, names further collections and
    receives what the layers sowed into each during this call
    (``{"counters": {}}`` comes back as ``{"counters": {layer: ...}}``):
    what a step counted or chose on the device leaves by this door, and
    the result stays the pair it was."""
    logits, mut = model.apply(variables, tokens, **kw,
                              mutable=["cache", *(sown or ())])
    for name in sown or ():
        sown[name] = unfreeze(mut.get(name, {}))
    return logits, unfreeze(mut["cache"])


def summed(sown: dict, names: tuple) -> list:
    """What the layers sowed under each of ``names`` into one collection,
    summed over the layers: an int32 [1] a name (none: an empty list,
    which a concatenation with a step's tokens leaves as they were)."""
    leaves = jax.tree_util.tree_leaves_with_path(sown)
    return [jnp.reshape(sum(leaf for path, leaf in leaves
                            if path[-2].key == name), (1,)).astype(jnp.int32)
            for name in names]


def prefill(model, variables: dict, tokens: jax.Array,
            lengths=None, sown: dict | None = None
            ) -> tuple[jax.Array, dict]:
    """Run the prompt through a ``decode=True`` model and return
    ``(logits [B, T, vocab], cache)``.  ``lengths`` ([B] or scalar) gives
    each row's true length when ``tokens`` is right-padded to a bucket:
    the write cursor rewinds to it, so the first decode_step overwrites
    the pad garbage and the mask hides the rest of it; the model gets it
    too (a recurrence cannot be rewound: a family with recurrent state
    stops there).  Row b's next-token logits are ``logits[b, lengths[b]
    - 1]``.  ``sown``: see ``_apply``."""
    logits, cache = _apply(model, variables, tokens, sown, lengths=lengths)
    if lengths is not None:
        cache = _with_cache_index(cache, lengths)
    return logits, cache


def fresh_cache(model, params, slots: int) -> dict:
    """``slots`` empty rows: zeros in every leaf, write cursors and a
    family's recurrent state too."""
    shapes = jax.eval_shape(
        lambda p: model.apply({"params": p},
                              jnp.zeros((slots, 1), jnp.int32),
                              mutable=["cache"])[1]["cache"], params)
    return unfreeze(jax.tree_util.tree_map(
        lambda leaf: jnp.zeros(leaf.shape, leaf.dtype), shapes))


def decode_step(model, variables: dict, cache: dict, tokens: jax.Array,
                sown: dict | None = None) -> tuple[jax.Array, dict]:
    """One incremental step of a ``decode=True`` model: ``tokens``
    [B, 1] (or [B]) → ``(logits [B, 1, vocab], updated cache)``.  Each
    row advances at its own depth, which is what lets continuous
    batching admit a fresh prefill into a half-decoded batch.
    ``sown``: see ``_apply``."""
    if tokens.ndim == 1:
        tokens = tokens[:, None]
    return _apply(model, {**variables, "cache": cache}, tokens, sown)


def paged_apply(model, variables: dict, cache: dict, tokens: jax.Array,
                block_tables, cursors,
                lengths=None) -> tuple[jax.Array, dict]:
    """One paged-cache apply (``decode=True, paged=True``): prefill and
    decode are the SAME call — ``tokens [B, T]`` (T = 1 for a decode
    step, a padded prompt bucket for prefill) write into the pool
    through each row's ``block_tables`` entry at its ``cursors``
    position and attend over the gathered prefix.  ``lengths`` keeps
    padded positions out of real blocks; an empty ``cache`` makes pools."""
    if tokens.ndim == 1:
        tokens = tokens[:, None]
    logits, mut = model.apply({**variables, "cache": cache}, tokens,
                              block_tables=block_tables,
                              cursors=cursors, lengths=lengths,
                              mutable=["cache"])
    return logits, unfreeze(mut["cache"])


def paged_copy_block(cache: dict, src: int, dst: int) -> dict:
    """The tensor half of a copy-on-write: pool row ``src`` to ``dst`` in
    every layer's pools (the id half is serving/kvpool.py's ``cow``)."""
    cache = unfreeze(cache)
    for key, node in paged_pool_leaves(cache):
        node[key] = node[key].at[dst].set(node[key][src])
    return cache


def paged_pool_leaves(cache: dict) -> list:
    """The per-layer key/value pools as (leaf name, parent dict), in an
    order that both ends of a block stream share (same cache tree)."""
    leaves = []

    def walk(node):
        if not isinstance(node, dict):
            return
        for key in sorted(node):
            if key in ("key_pool", "value_pool"):
                leaves.append((key, node))
            else:
                walk(node[key])
    walk(cache)
    return leaves
