"""The delta-rule recurrence of a Kimi Delta Attention layer
(arXiv:2510.26692), in its two forms.

For every head (keys of ``K`` channels, values of ``V``, a state ``S`` of
``K x V`` numbers, float32), with ``a_t = exp(g_t)`` in (0, 1)^K a decay
a key channel and ``b_t`` a scalar write strength:

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

which is ``S' = Diag(a_t) S_{t-1}``, ``u_t = b_t (v_t - S'^T k_t)``,
``S_t = S' + k_t u_t^T``: the state forgets, is read at ``k_t``, and is
corrected towards ``v_t`` there (the delta rule).

- ``kda_scan``: the chunked form over a whole prompt (prefill).  In a
  chunk of ``C`` positions, with ``G_i`` the running sum of ``g`` and
  ``S_0`` the state entering the chunk,

      A_ij = b_i sum_c k_ic k_jc exp(G_ic - G_jc)            (j < i)
      U    = (I + A)^-1 Diag(b) (V - (K * exp(G)) S_0)
      o_i  = (q_i * exp(G_i))^T S_0
             + sum_{j<=i} [sum_c q_ic k_jc exp(G_ic - G_jc)] u_j
      S_C  = Diag(exp(G_C)) S_0 + sum_j (k_j * exp(G_C - G_j)) u_j^T

  in plain einsums, float32; a ``lax.scan`` carries the state from
  chunk to chunk.  The decays are taken as differences before the
  exponential (``exp(G_i - G_j)`` with ``j <= i`` is at most 1): the
  factored form ``exp(G_i) exp(-G_j)`` overflows where a channel
  forgets fast.  With ``lengths`` a padded position has ``g = 0`` and
  ``b = 0`` and leaves ``S`` as it was, as ``ssm_scan`` does it.
- ``kda_update``: one step for a batch of slots (decode).  On a TPU it
  is one Pallas kernel, named ``hvd.kda_update`` as ``hvd.ssm_update``
  carries its name, which reads and writes each slot's state once, in
  place.  ``kda_update_plain`` is the same step in plain ``jax.numpy``:
  what runs elsewhere, and the kernel's reference.

The state is stored as the equations have it, ``[slots, H, K, V]``:
with ``V = 128`` a row is the 128 lanes, a key channel a sublane, and
both reductions (``S'^T k`` and ``S^T q``) run down the sublanes.
``interpret=True`` runs the kernel interpreted (the unit tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST
_VMEM_BYTES = 32 << 20      # a block of state in and out, twice each


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Prefill: the chunked scan
# ---------------------------------------------------------------------------
def kda_scan(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
             b: jax.Array, *, chunk: int,
             lengths=None) -> tuple[jax.Array, jax.Array]:
    """``q`` and ``k`` [B, T, H, K] (normalised, ``q`` scaled), ``v``
    [B, T, H, V], ``g`` [B, T, H, K] (the log decay, at most 0), ``b``
    [B, T, H] -> ``(o [B, T, H, V], state [B, H, K, V])``, both float32;
    the state is the one after position ``lengths - 1`` of each row
    (``T - 1`` without ``lengths``), and ``o`` past a row's length is
    garbage."""
    with jax.named_scope("hvd.kda_scan"):
        bsz, t, h, dk = k.shape
        dv = v.shape[-1]
        q, k, v, g, b = (x.astype(jnp.float32) for x in (q, k, v, g, b))
        if lengths is not None:
            live = jnp.arange(t)[None, :] \
                < jnp.reshape(jnp.asarray(lengths, jnp.int32), (-1, 1))
            g = jnp.where(live[..., None, None], g, 0.0)
            b = jnp.where(live[..., None], b, 0.0)
        chunk = min(chunk, t)
        pad = -t % chunk
        if pad:                 # g = 0, b = 0: the padding changes nothing
            q, k, v, g, b = (jnp.pad(x, [(0, 0), (0, pad)]
                                     + [(0, 0)] * (x.ndim - 2))
                             for x in (q, k, v, g, b))
        z = (t + pad) // chunk

        def by_chunk(x):        # [B, T, H, ...] -> [Z, B, H, C, ...]
            x = x.reshape(bsz, z, chunk, *x.shape[2:])
            return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

        causal = jnp.tril(jnp.ones((chunk, chunk), bool))
        strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
        eye = jnp.eye(chunk, dtype=jnp.float32)

        def one_chunk(state, xs):
            qc, kc, vc, gc, bc = xs        # [B, H, C, K | V], bc [B, H, C]
            log = jnp.cumsum(gc, axis=2)   # G: up to and with each position
            # exp(G_i - G_j) for j <= i, 0 above the diagonal: [B,H,i,j,K].
            decay = jnp.exp(jnp.where(
                causal[:, :, None],
                log[:, :, :, None, :] - log[:, :, None, :, :], -jnp.inf))
            kk = jnp.sum(kc[:, :, :, None, :] * kc[:, :, None, :, :] * decay,
                         axis=-1)
            qk = jnp.sum(qc[:, :, :, None, :] * kc[:, :, None, :, :] * decay,
                         axis=-1)
            a = bc[..., None] * jnp.where(strict, kk, 0.0)
            into = jnp.exp(log)            # from the chunk's start
            rhs = bc[..., None] * (vc - jnp.einsum(
                "bhic,bhcv->bhiv", kc * into, state, precision=_HIGHEST))
            u = jax.scipy.linalg.solve_triangular(
                eye + a, rhs, lower=True, unit_diagonal=True)
            o = jnp.einsum("bhic,bhcv->bhiv", qc * into, state,
                           precision=_HIGHEST) \
                + jnp.einsum("bhij,bhjv->bhiv", qk, u, precision=_HIGHEST)
            to_end = jnp.exp(log[:, :, -1:, :] - log)
            state = into[:, :, -1, :, None] * state + jnp.einsum(
                "bhjc,bhjv->bhcv", kc * to_end, u, precision=_HIGHEST)
            return state, o

        state, o = jax.lax.scan(
            one_chunk, jnp.zeros((bsz, h, dk, dv), jnp.float32),
            tuple(by_chunk(x) for x in (q, k, v, g, b)))
        # [Z, B, H, C, V] -> [B, T, H, V]
        o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)
        return o.reshape(bsz, t + pad, h, dv)[:, :t], state


# ---------------------------------------------------------------------------
# Decode: one step of the recurrence for every slot
# ---------------------------------------------------------------------------
def kda_update_plain(state: jax.Array, q: jax.Array, k: jax.Array,
                     v: jax.Array, g: jax.Array, b: jax.Array
                     ) -> tuple[jax.Array, jax.Array]:
    """``state`` [B, H, K, V] float32, ``q``, ``k`` and ``g`` [B, H, K],
    ``v`` [B, H, V], ``b`` [B, H] -> ``(o [B, H, V] float32, new
    state)``."""
    q, k, v, g, b = (x.astype(jnp.float32) for x in (q, k, v, g, b))
    state = jnp.exp(g)[..., None] * state
    u = b[..., None] * (v - jnp.sum(state * k[..., None], axis=2))
    state = state + k[..., None] * u[:, :, None, :]
    return jnp.sum(state * q[..., None], axis=2), state


def _update_kernel(state_ref, cols_ref, v_ref, b_ref, o_ref, new_ref, *,
                   heads: int):
    """One slot, ``heads`` heads.  ``cols`` [K, 3 * heads] has the decay,
    the key and the query of each head as columns (a key channel a
    sublane, as in the state), so each is broadcast once along the lanes;
    ``v``, ``b`` and ``o`` are rows along the lanes, and both reductions
    run down the sublanes."""
    cols = cols_ref[0, 0]
    v, b = v_ref[0], b_ref[0]                           # [heads, V]
    shape = state_ref.shape[2:]

    def column(j):
        return jnp.broadcast_to(cols[:, j:j + 1], shape)

    for i in range(heads):
        at = slice(i, i + 1)
        k = column(heads + i)
        state = state_ref[0, i] * column(i)                     # forget
        u = b[at] * (v[at] - jnp.sum(state * k, axis=0, keepdims=True))
        state = state + k * u                                   # correct
        new_ref[0, i] = state
        o_ref[0, at, :] = jnp.sum(state * column(2 * heads + i), axis=0,
                                  keepdims=True)


# Jitted, so that a model's layers share one traced and one lowered
# kernel (ops/decode_attention.py says what lowering one a layer costs).
@functools.partial(jax.jit, static_argnames=("block_heads", "interpret"))
def _kda_update_pallas(state, q, k, v, g, b, *, block_heads: int,
                       interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, h, dk, dv = state.shape
    heads = min(block_heads, h)
    assert h % heads == 0, f"{h} heads do not divide by {heads}"
    groups = h // heads
    # [slots, groups, K, 3 * heads]: decay, key, query, a column a head.
    cols = jnp.stack([jnp.exp(g), k, q], axis=1) \
        .reshape(slots, 3, groups, heads, dk).transpose(0, 2, 4, 1, 3) \
        .reshape(slots, groups, dk, 3 * heads)
    block = pl.BlockSpec((1, heads, dk, dv), lambda s, j: (s, j, 0, 0))
    rows = pl.BlockSpec((1, heads, dv), lambda s, j: (s, j, 0))
    o, state = pl.pallas_call(
        functools.partial(_update_kernel, heads=heads),
        grid=(slots, groups),
        in_specs=[block,
                  pl.BlockSpec((1, 1, dk, 3 * heads),
                               lambda s, j: (s, j, 0, 0)),
                  rows, rows],
        out_specs=[rows, block],
        out_shape=[jax.ShapeDtypeStruct((slots, h, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={0: 1},         # the state, in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name="hvd.kda_update",
    )(state, cols, v, jnp.broadcast_to(b[..., None], (slots, h, dv)))
    return o, state


def kda_update(state: jax.Array, q: jax.Array, k: jax.Array, v: jax.Array,
               g: jax.Array, b: jax.Array, *, block_heads: int = 16,
               interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """One step of the recurrence for every slot (shapes as
    ``kda_update_plain``): the kernel on a TPU or interpreted, the plain
    form elsewhere.  The state is read and written once; a caller that
    donates it has it updated in place."""
    with jax.named_scope("hvd.kda_update"):
        if not (_on_tpu() or interpret):
            return kda_update_plain(state, q, k, v, g, b)
        q, k, v, g, b = (x.astype(jnp.float32) for x in (q, k, v, g, b))
        return _kda_update_pallas(state, q, k, v, g, b,
                                  block_heads=block_heads,
                                  interpret=interpret)
