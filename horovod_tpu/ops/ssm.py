"""The selective-state recurrence of a Mamba-2 mixer, in its two forms.

For every head ``h`` (``P`` channels, a state of ``P x N`` numbers, one
scalar decay a position) and one group of ``B``/``C`` projections:

    S_t[h] = exp(dt_t[h] * A[h]) * S_{t-1}[h] + dt_t[h] * x_t[h] (outer) B_t
    y_t[h] = S_t[h] C_t + D[h] * x_t[h]

- ``ssm_scan``: the chunked form over a whole prompt (prefill).  Within
  a chunk everything is a matrix product; between chunks a scan carries
  the chunk states.  With ``lengths`` the recurrence stops at each row's
  true length: a padded position has ``dt = 0``, which makes its decay 1
  and its input 0, so it leaves ``S`` as it was.
- ``ssm_update``: one step for a batch of slots (decode).  On a TPU it
  is one Pallas kernel, named ``hvd.ssm_update`` the way the flash
  kernels carry their names (a device trace selects an operation by
  ``<opcode> <name>`` only), which reads and writes the state once, in
  place.  Elsewhere, and as the kernel's reference, ``ssm_update_plain``
  is the same step in plain ``jax.numpy``.

The state is float32 everywhere: the recurrence sums over thousands of
steps.  Between calls it is stored transposed and packed
(``state_shape``: ``[slots, H / pack, N, pack * P]``), so that the
kernel's every operand is dense along the 128 lanes; ``pack_state`` and
``unpack_state`` convert from and to the equations' ``[B, H, P, N]``.
``interpret=True`` runs the kernel interpreted (the unit tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Prefill: the chunked scan
# ---------------------------------------------------------------------------
def ssm_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, d: jax.Array, *, chunk: int,
             lengths=None) -> tuple[jax.Array, jax.Array]:
    """``x`` [B, T, H, P], ``dt`` [B, T, H] (positive, after its
    softplus), ``a`` [H] (negative), ``b`` and ``c`` [B, T, N], ``d`` [H]
    -> ``(y [B, T, H, P], state)``, both float32; the state, in the
    stored layout (``state_shape``), is the one after position
    ``lengths - 1`` of each row (``T - 1`` without ``lengths``), and
    ``y`` past a row's length is garbage."""
    with jax.named_scope("hvd.ssm_scan"):
        bsz, t, h, p = x.shape
        n = b.shape[-1]
        x, dt, b, c = (v.astype(jnp.float32) for v in (x, dt, b, c))
        if lengths is not None:
            live = jnp.arange(t)[None, :] \
                < jnp.reshape(jnp.asarray(lengths, jnp.int32), (-1, 1))
            dt = jnp.where(live[..., None], dt, 0.0)
        chunk = min(chunk, t)
        pad = -t % chunk
        if pad:                      # dt = 0: the padding changes nothing
            x, dt, b, c = (jnp.pad(v, [(0, 0), (0, pad)]
                                   + [(0, 0)] * (v.ndim - 2))
                           for v in (x, dt, b, c))
        z = (t + pad) // chunk
        xdt = (x * dt[..., None]).reshape(bsz, z, chunk, h, p)
        xs = x.reshape(bsz, z, chunk, h, p)
        bs, cs = b.reshape(bsz, z, chunk, n), c.reshape(bsz, z, chunk, n)
        # Log decay from a chunk's start up to and with each position.
        log = jnp.cumsum((dt * a).reshape(bsz, z, chunk, h), axis=2)

        # Inside a chunk: position i reads every j <= i of its chunk.
        causal = jnp.tril(jnp.ones((chunk, chunk), bool))
        between = log[:, :, :, None, :] - log[:, :, None, :, :]
        decay = jnp.exp(jnp.where(causal[None, None, :, :, None],
                                  between, -jnp.inf))        # [B,Z,i,j,H]
        scores = jnp.einsum("bzin,bzjn->bzij", cs, bs, precision=_HIGHEST)
        y = jnp.einsum("bzijh,bzjhp->bzihp", scores[..., None] * decay,
                       xdt, precision=_HIGHEST)

        # What a chunk adds to the state by its end, and the scan over
        # the chunks; ``before`` is the state each chunk starts from.
        to_end = jnp.exp(log[:, :, -1:, :] - log)            # [B,Z,j,H]
        added = jnp.einsum("bzjh,bzjhp,bzjn->bzhpn", to_end, xdt, bs,
                           precision=_HIGHEST)
        whole = jnp.exp(log[:, :, -1, :])                    # [B,Z,H]

        def carry(state, step):
            kept, new = step
            return kept[..., None, None] * state + new, state

        state, before = jax.lax.scan(
            carry, jnp.zeros((bsz, h, p, n), jnp.float32),
            (whole.swapaxes(0, 1), added.swapaxes(0, 1)))
        y = y + jnp.einsum("bzin,bzhpn,bzih->bzihp", cs,
                           before.swapaxes(0, 1), jnp.exp(log),
                           precision=_HIGHEST)
        y = y + d[:, None] * xs
        return y.reshape(bsz, t + pad, h, p)[:, :t], pack_state(state)


# ---------------------------------------------------------------------------
# The state's layout
# ---------------------------------------------------------------------------
def heads_packed(heads: int, p: int) -> int:
    """How many heads share the 128 lanes of a row of the stored state:
    as many as fit beside each other and divide ``heads`` (2 at P = 64)."""
    pack = max(1, 128 // p)
    while heads % pack:
        pack -= 1
    return pack


def state_shape(slots: int, heads: int, p: int, n: int) -> tuple:
    """The stored state: ``[slots, H / pack, N, pack * P]``, the state
    matrices of ``pack`` heads transposed and laid beside each other.  A
    lane is then one ``(head, p)`` channel and a sublane one of the ``N``
    state numbers: ``x``, ``dt``, ``A``, ``D`` and ``y`` are plain rows
    along the lanes, and the read-out ``S C`` sums over sublanes."""
    pack = heads_packed(heads, p)
    return (slots, heads // pack, n, pack * p)


def pack_state(state: jax.Array) -> jax.Array:
    """``[B, H, P, N]``, as the equations have it, to the stored layout."""
    bsz, h, p, n = state.shape
    pack = heads_packed(h, p)
    return state.reshape(bsz, h // pack, pack, p, n) \
        .transpose(0, 1, 4, 2, 3).reshape(state_shape(bsz, h, p, n))


def unpack_state(state: jax.Array, heads: int) -> jax.Array:
    """The stored layout back to ``[B, H, P, N]``."""
    bsz, groups, n, lanes = state.shape
    pack = heads // groups
    return state.reshape(bsz, groups, n, pack, lanes // pack) \
        .transpose(0, 1, 3, 4, 2).reshape(bsz, heads, lanes // pack, n)


def _rows(v: jax.Array, p: int, groups: int) -> jax.Array:
    """A number a head, ``[..., H]``, repeated over its ``P`` channels
    and cut into the state's rows: ``[..., groups, lanes]``."""
    v = jnp.broadcast_to(v[..., None], (*v.shape, p))
    return v.reshape(*v.shape[:-2], groups, -1)


# ---------------------------------------------------------------------------
# Decode: one step of the recurrence for every slot
# ---------------------------------------------------------------------------
def ssm_update_plain(state: jax.Array, x: jax.Array, dt: jax.Array,
                     a: jax.Array, b: jax.Array, c: jax.Array,
                     d: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``state`` in the stored layout (``state_shape``), float32, ``x``
    [B, H, P], ``dt`` [B, H], ``a`` and ``d`` [H], ``b`` and ``c``
    [B, N] -> ``(y [B, H, P] float32, new state)``."""
    bsz, h, p = x.shape
    groups = state.shape[1]
    x, dt, b, c = (v.astype(jnp.float32) for v in (x, dt, b, c))
    xs, dts = x.reshape(bsz, groups, -1), _rows(dt, p, groups)
    state = jnp.exp(dts * _rows(a, p, groups))[:, :, None, :] * state \
        + b[:, None, :, None] * (xs * dts)[:, :, None, :]
    y = jnp.sum(state * c[:, None, :, None], axis=2) \
        + _rows(d, p, groups) * xs
    return y.reshape(bsz, h, p), state


def _update_kernel(state_ref, x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref,
                   y_ref, new_ref, *, groups: int):
    """One slot, ``groups`` rows of heads.  Everything is lane-dense: a
    row's ``x``, ``dt``, ``A``, ``D`` broadcast along the sublanes, ``B``
    and ``C`` are columns broadcast once along the lanes, and ``y`` is a
    sum over sublanes."""
    x, dt = x_ref[0], dt_ref[0]                         # [groups, lanes]
    decay = jnp.exp(dt * a_ref[...])
    xdt = x * dt
    skip = d_ref[...] * x
    n, lanes = state_ref.shape[2:]
    b = jnp.broadcast_to(b_ref[0], (n, lanes))          # from [N, 1]
    c = jnp.broadcast_to(c_ref[0], (n, lanes))
    for g in range(groups):
        at = slice(g, g + 1)
        new = state_ref[0, g] * decay[at] + b * xdt[at]          # [N, lanes]
        new_ref[0, g] = new
        y_ref[0, at, :] = jnp.sum(new * c, axis=0, keepdims=True) + skip[at]


def _ssm_update_pallas(state, x, dt, a, b, c, d, *, block_groups: int,
                       interpret: bool):
    from jax.experimental import pallas as pl

    slots, rows, n, lanes = state.shape
    h, p = x.shape[1:]
    groups = min(block_groups, rows)
    assert rows % groups == 0, f"{rows} rows do not divide by {groups}"
    small = pl.BlockSpec((1, groups, lanes), lambda s, g: (s, g, 0))
    fixed = pl.BlockSpec((groups, lanes), lambda s, g: (g, 0))
    column = pl.BlockSpec((1, n, 1), lambda s, g: (s, 0, 0))
    block = pl.BlockSpec((1, groups, n, lanes), lambda s, g: (s, g, 0, 0))
    y, state = pl.pallas_call(
        functools.partial(_update_kernel, groups=groups),
        grid=(slots, rows // groups),
        in_specs=[block, small, small, fixed, fixed, column, column],
        out_specs=[small, block],
        out_shape=[jax.ShapeDtypeStruct((slots, rows, lanes), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={0: 1},         # the state, in place
        interpret=interpret,
        name="hvd.ssm_update",
    )(state, x.reshape(slots, rows, lanes), _rows(dt, p, rows),
      _rows(a, p, rows), _rows(d, p, rows), b[:, :, None], c[:, :, None])
    return y.reshape(slots, h, p), state


def ssm_update(state: jax.Array, x: jax.Array, dt: jax.Array, a: jax.Array,
               b: jax.Array, c: jax.Array, d: jax.Array, *,
               block_groups: int = 16, interpret: bool = False
               ) -> tuple[jax.Array, jax.Array]:
    """One step of the recurrence for every slot (shapes as
    ``ssm_update_plain``): the kernel on a TPU or interpreted, the plain
    form elsewhere.  The state is read and written once; a caller that
    donates it has it updated in place."""
    with jax.named_scope("hvd.ssm_update"):
        if not (_on_tpu() or interpret):
            return ssm_update_plain(state, x, dt, a, b, c, d)
        x, dt, a, b, c, d = (v.astype(jnp.float32)
                             for v in (x, dt, a, b, c, d))
        return _ssm_update_pallas(state, x, dt, a, b, c, d,
                                  block_groups=block_groups,
                                  interpret=interpret)
