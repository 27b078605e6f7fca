"""The slot cache of one replica: where the keys, values and recurrent
state of its in-flight requests live on the device, in one of two
layouts behind one interface.

``ReplicaExecutor`` picks a class once, from ``ServeConfig.paged``, and
asks only: ``warm`` (compile every program, note the cache's aliasing),
``admit`` (enqueue a prompt's prefill into a slot; its first token stays
on the device until ``first_token``), ``decode`` (enqueue one step for
the slot array), ``fetch`` (wait for a step's result), ``release``,
``fresh``, ``kv_stats``, ``close``, ``block_capacity`` for the batcher.
``tree`` is the cache: every program that writes it takes it donated, so
it runs behind the one before, and rebinds its result, so nobody else
may hold it.  ``result``, the last decode step's, stays on the device:
the next step reads a slot's input token from it where the host has none
newer.  The model is reached through its family (``models/family.py``).

- :class:`DenseSlotCache`: the slot on axis 0 of every leaf; an
  admission prefills one row and inserts it as row ``slot``.
- :class:`PagedSlotCache`: per-slot block tables into a
  :class:`~.kvpool.KVBlockPool`; prefix reuse by content address,
  copy-on-write, LRU eviction.  It alone offers what disaggregated
  prefill streams: ``holds_prompt``, ``prefill_image``, ``land``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..common.logging import logger
from ..models.kvcache import later_pass, summed
from ..ops import decode_attention, mla
from ..telemetry.spans import span
from .kvpool import FNV_SEED, KVBlockPool, chain_hash


def prompt_bucket(cfg, n: int) -> int:
    """The compiled prefill shape ``n`` prompt tokens pad to: the next
    power of two from 8 (at most ``max_seq``), or a warm-up bucket
    between ``n`` and it where the configuration lists one (A.X-K1's
    6,144 and 10,240)."""
    power = min(max(8, 1 << max(0, (n - 1)).bit_length()), cfg.max_seq)
    return min((b for b in cfg.warmup_buckets if n <= b <= power),
               default=power)


def _padded(cfg, toks: list) -> np.ndarray:
    padded = np.zeros((1, prompt_bucket(cfg, len(toks))), np.int32)
    padded[0, :len(toks)] = toks
    return padded


def _sample(logits):
    """Greedy sampling: the arg-max over the vocabulary axis."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


class _SlotCache:
    """What the layouts share: warm-up and the aliasing counters."""
    block_capacity = 0         # blocks the batcher reserves from; 0 = none

    def __init__(self, cfg, family, model, stats: dict) -> None:
        self.cfg, self.family, self.model = cfg, family, model
        self.stats = stats             # the executor's own
        stats.update(dict.fromkeys(family.decode_counters, 0))
        # Jitted like every other model call here: un-jitted, each of its
        # hundreds of small ops compiles and dispatches on its own.
        self._init_cache_jit = jax.jit(self._init_cache_impl)
        # What a decode step's attention reads of one slot, a kind of
        # attention layer: (layers, span, block): ``span`` positions at
        # most (a window layer's ring holds no more), all of them or,
        # with the kernel (ops/decode_attention.py), the slot's live
        # length rounded up to ``block``.
        self._attend_kinds = [(1, cfg.max_seq, 0)]
        self.tree = None
        # The last decode step's result (a token a slot, then the
        # family's counters), fetched or not.
        self.result = jnp.zeros(
            cfg.slots + len(family.decode_counters), jnp.int32)

    def fresh(self, params) -> None:
        self.tree = self._init_cache_jit(params)

    def _count_prefill(self, tokens: int, positions: int) -> None:
        """One prefill: the prompt tokens it computes anew and the
        positions its program runs over (their bucket)."""
        stats = self.stats
        stats["prefill_prompt_tokens"] = \
            stats.get("prefill_prompt_tokens", 0) + tokens
        stats["prefill_bucket_positions"] = \
            stats.get("prefill_bucket_positions", 0) + positions

    def warm(self, params, last_tokens: np.ndarray) -> None:
        """Compile every program the serve loop runs.  Each of them takes
        the cache donated, so each call's result is rebound: the leaves
        a program was given are deleted once it is enqueued."""
        for bucket in self.cfg.warmup_buckets:
            if bucket <= self.cfg.max_seq:
                self._warm_prefill(params, [0] * bucket)
        decode, args = self._decode_call(
            params, last_tokens, np.ones(self.cfg.slots, bool))
        self._note_cache_aliasing(decode.lower(*args).compile())
        self.result, self.tree = decode(*args)
        jax.block_until_ready(self.result)
        self.tree = None               # one copy at a time
        self.fresh(params)             # discard warmup cache writes

    def decode(self, params, last_tokens: np.ndarray,
               from_host: np.ndarray, active: list, slots: list):
        """Enqueue one step for the whole slot array; its result stays
        on the device.  A slot's input token is ``last_tokens``' where
        ``from_host`` says so (it was admitted since the last step, or
        there was none) and the last step's own result elsewhere, which
        nobody need have fetched yet."""
        decode, args = self._decode_call(params, last_tokens, from_host)
        self.result, self.tree = decode(*args)
        # This step's write is read too: a slot's live context is its
        # length after it.
        lengths = np.fromiter((slots[i].seq_len + 1 for i in active),
                              np.int64, len(active))
        # A layer's worth, the mean over the attention layers: a window
        # layer's live context ends at its ring's span.  The grid steps:
        # a kernel's, one a live block (ops/decode_attention.py:
        # live_blocks), of the span's blocks a slot; the plain form
        # reads a slot's whole span, one step of one.
        live = read = steps = full = 0
        for layers, span, block in self._attend_kinds:
            within = np.minimum(lengths, span)
            live += layers * int(within.sum())
            positions = decode_attention.read_positions(within, span, block)
            read += layers * positions
            unit = block or span
            steps += layers * positions // unit
            full += layers * len(within) * (span // unit)
        layers = sum(kind[0] for kind in self._attend_kinds)
        stats = self.stats
        for key, count in (("attend_live_positions", live),
                           ("attend_read_positions", read),
                           ("attend_grid_steps", steps),
                           ("attend_grid_full", full)):
            stats[key] = stats.get(key, 0) + count // layers
        return self.result

    def fetch(self, result) -> np.ndarray:
        """Wait for a step's tokens, one a slot.  What the decode
        program counted on the device (the family's ``decode_counters``;
        none for most) rides behind them in the same array: added up in
        ``stats`` here."""
        fetched = np.asarray(result)
        for name, value in zip(self.family.decode_counters,
                               fetched[self.cfg.slots:]):
            self.stats[name] += int(value)
        return fetched[:self.cfg.slots]

    def first_token(self, first) -> int:
        """Wait for the prefill ``admit`` enqueued: its first token."""
        with span("serve.first_token_fetch"):
            return int(first)          # waits for the device

    def _input_tokens(self, result, last_tokens, from_host):
        """Inside the decode program: each slot's input token, [slots,
        1], the host's where ``from_host`` and else the last step's."""
        return jnp.where(from_host, last_tokens,
                         result[:self.cfg.slots])[:, None]

    def _note_cache_aliasing(self, decode_program) -> None:
        """How much of the cache the compiled decode program updates in
        place: the cache is its only donated argument, so what it
        aliases from input to output is cache."""
        leaves = jax.tree_util.tree_flatten_with_path(self.tree)[0]
        stats = self.stats
        stats["cache_bytes"] = sum(leaf.nbytes for _, leaf in leaves)
        stats["state_bytes"] = sum(
            leaf.nbytes for path, leaf in leaves
            if path[-1].key in self.family.state_leaves)
        # Of the rest (keys, values, cursors), the window layers' rings,
        # and a looped stack's leaves of the passes after its first.
        stats["window_bytes"] = sum(
            leaf.nbytes for path, leaf in leaves
            if path[-1].key in ("ring_key", "ring_value"))
        stats["loop_cache_bytes"] = sum(
            leaf.nbytes for path, leaf in leaves
            if later_pass(tuple(k.key for k in path)))
        stats["cache_aliased_bytes"] = \
            decode_program.memory_analysis().alias_size_in_bytes
        logger.info("serving: slot cache %.2f of %.2f GB aliased by the "
                    "decode program (%d of %d bytes)",
                    stats["cache_aliased_bytes"] / 1e9,
                    stats["cache_bytes"] / 1e9,
                    stats["cache_aliased_bytes"], stats["cache_bytes"])

    def release(self, slot: int) -> None:
        """Nothing to give back: the next admission replaces the row."""

    def kv_stats(self) -> dict | None:
        return None

    def close(self) -> None:
        pass


class DenseSlotCache(_SlotCache):
    """The family's dense cache of ``cfg.slots`` rows."""

    def __init__(self, cfg, family, model, stats: dict) -> None:
        super().__init__(cfg, family, model, stats)
        self._decode_jit = jax.jit(self._decode_impl, donate_argnums=1)
        self._prefill_jit = jax.jit(self._prefill_impl)
        self._insert_jit = jax.jit(self._insert_impl, donate_argnums=0)

    def _decode_impl(self, params, cache, result, last_tokens, from_host):
        sown = {"counters": {}}
        logits, cache = self.family.decode_step(
            self.model, {"params": params}, cache,
            self._input_tokens(result, last_tokens, from_host), sown=sown)
        return jnp.concatenate([
            _sample(logits[:, -1, :]),
            *summed(sown["counters"], self.family.decode_counters)]), cache

    def _prefill_impl(self, params, tokens, n):
        logits, cache = self.family.prefill(
            self.model, {"params": params}, tokens, lengths=n)
        return _sample(logits[0, n - 1, :]), cache

    @staticmethod
    def _insert_impl(cache, cache1, slot):
        """Row ``slot`` of every leaf of the slot cache becomes the
        prefilled request's only row (keys, values, write cursor and a
        family's recurrent state alike: nothing of the slot's last
        occupant is left); ``slot`` is traced: one program for all."""
        return jax.tree_util.tree_map(
            lambda big, small: jax.lax.dynamic_update_slice_in_dim(
                big, small, slot, axis=0), cache, cache1)

    def _init_cache_impl(self, params):
        return self.family.fresh_cache(self.model, params, self.cfg.slots)

    def fresh(self, params) -> None:
        super().fresh(params)
        # The span and the block the decode program's attention reads
        # each layer's key and value leaves (a latent layer's one leaf)
        # in, by kind of layer; and the layers whose kernel writes the
        # step's row itself (a layer's sink lies beside its leaves, in
        # the parameters; ``hvd.mla_decode`` writes wherever it runs).
        kinds: dict = {}
        fused = 0
        leaves = {tuple(k.key for k in path): leaf for path, leaf in
                  jax.tree_util.tree_flatten_with_path(self.tree)[0]}
        for path, keys in leaves.items():
            if path[-1] == "latent":
                kind = (keys.shape[1], mla.kernel_block(keys.shape,
                                                        keys.dtype))
                kinds[kind] = kinds.get(kind, 0) + 1
                fused += bool(kind[1])
            elif path[-1] in ("cached_key", "ring_key"):
                values = leaves[(*path[:-1],
                                 path[-1].replace("key", "value"))]
                kind = (keys.shape[1], decode_attention.kernel_block(
                    keys.shape, keys.dtype, values=values.shape))
                kinds[kind] = kinds.get(kind, 0) + 1
                layer = params          # a later pass's leaves: its layer's
                for key in path[:-1]:
                    if not later_pass((key,)):
                        layer = layer.get(key, {})
                fused += decode_attention.kernel_writes(
                    keys.shape, keys.dtype, values.shape, "sink" in layer)
        self._attend_kinds = [(layers, span, block)
                              for (span, block), layers in kinds.items()]
        self.stats["attend_layers"] = sum(kinds.values())
        self.stats["attend_write_fused_layers"] = fused

    def _warm_prefill(self, params, toks: list) -> None:
        # The insert compiles once.
        jax.block_until_ready(self.admit(params, 0, toks, 1))

    def _decode_call(self, params, last_tokens, from_host):
        # Copies: the host's arrays change while the step is in flight.
        return self._decode_jit, (params, self.tree, self.result,
                                  last_tokens.copy(), from_host.copy())

    def admit(self, params, slot: int, toks: list, max_new: int):
        """Enqueue the prefill of ``toks`` and its insert as row ``slot``:
        the first token, on the device.  The insert takes the cache
        donated, so it runs behind the decode step in flight, which
        writes the cache it is given; the prefill reads no cache."""
        padded = _padded(self.cfg, toks)
        self._count_prefill(len(toks), padded.shape[1])
        with span("serve.prefill_dispatch"):
            first, cache1 = self._prefill_jit(
                params, jnp.asarray(padded), jnp.int32(len(toks)))
        with span("serve.cache_insert"):     # a dispatch: nothing waits
            self.tree = self._insert_jit(self.tree, cache1, np.int32(slot))
        return first


class PagedSlotCache(_SlotCache):
    """The block pool (id bookkeeping), the per-slot block tables and
    cursors (the model's addressing arguments), each slot's block list
    (physical ids in logical order, each held once by the slot) and the
    pools themselves (``tree``)."""

    def __init__(self, cfg, family, model, stats: dict) -> None:
        super().__init__(cfg, family, model, stats)
        self._sink = self.block_capacity = cfg.resolved_pool_blocks
        self._attend_kinds = [(1, cfg.table_width * cfg.block_tokens, 0)]
        self.pool = KVBlockPool(self._sink, cfg.block_tokens)
        self._tables = np.full((cfg.slots, cfg.table_width), self._sink,
                               np.int32)
        self._cursors = np.zeros(cfg.slots, np.int32)
        self._blocks: list[list] = [[] for _ in range(cfg.slots)]
        self._paged_jit = jax.jit(self._paged_impl, donate_argnums=1)
        self._paged_prefill_jit = jax.jit(self._paged_prefill_impl,
                                          donate_argnums=1)
        self._copy_block_jit = jax.jit(family.paged_copy_block,
                                       donate_argnums=0)

    def _paged_impl(self, params, cache, result, last_tokens, from_host,
                    tables, cursors):
        """One paged decode step for the whole slot array: inactive
        slots' tables point at the pool sink row, so their writes land
        in garbage space and their outputs are ignored."""
        logits, cache = self.family.paged_apply(
            self.model, {"params": params}, cache,
            self._input_tokens(result, last_tokens, from_host), tables,
            cursors)
        return _sample(logits[:, -1, :]), cache

    def _paged_prefill_impl(self, params, cache, tokens, table, cursor,
                            n):
        """Paged prefill of ONE request (B=1) straight into the shared
        pool through the slot's block table; ``cursor`` > 0 resumes
        past prefix-cache hits and ``n`` masks the padded tail."""
        logits, cache = self.family.paged_apply(
            self.model, {"params": params}, cache, tokens, table,
            cursor, lengths=n)
        return _sample(logits[0, n[0] - 1, :]), cache

    def _init_cache_impl(self, params):
        """One apply creates the pools; its only write is the sink's."""
        return self.family.paged_apply(
            self.model, {"params": params}, {},
            jnp.zeros((1, 1), jnp.int32), self._row([])[None],
            jnp.zeros((1,), jnp.int32))[1]

    def _row(self, blocks: list) -> np.ndarray:
        """A block table row: ``blocks``, then the sink."""
        row = np.full(self.cfg.table_width, self._sink, np.int32)
        row[:len(blocks)] = blocks
        return row

    def _prefill(self, params, toks: list, blocks: list, pos: int):
        """Enqueue the prefill of ``toks[pos:]`` through ``blocks``."""
        rem = toks[pos:]
        first, self.tree = self._paged_prefill_jit(
            params, self.tree, jnp.asarray(_padded(self.cfg, rem)),
            jnp.asarray(self._row(blocks)[None]),
            jnp.asarray([pos], np.int32), jnp.asarray([len(rem)], np.int32))
        return first

    def _warm_prefill(self, params, toks: list) -> None:
        jax.block_until_ready(self._prefill(params, toks, [], 0))

    def _decode_call(self, params, last_tokens, from_host):
        # Copies: the host's arrays change while the step is in flight.
        return self._paged_jit, (
            params, self.tree, self.result, last_tokens.copy(),
            from_host.copy(), self._tables.copy(), self._cursors.copy())

    # -- prefix cache ----------------------------------------------------
    def _lookup_prefix(self, toks: list) -> tuple[list, int]:
        """Walk the prompt's block chain through the prefix cache: (hit
        block ids, their refcounts already bumped; tokens covered)."""
        bt = self.cfg.block_tokens
        parent = FNV_SEED
        hits: list[int] = []
        pos = 0
        while pos < len(toks):
            seg = toks[pos:pos + bt]
            blk = self.pool.lookup(parent, seg)
            if blk is None:
                break
            hits.append(blk)
            parent = chain_hash(parent, seg)
            pos += len(seg)
        return hits, pos

    def _publish_prompt(self, toks: list, blocks: list) -> None:
        """Content-address every prompt block (full ones and the partial
        tail) so later identical prefixes hit.  Publishing makes a block
        immutable: the next write into the tail copies it first."""
        bt = self.cfg.block_tokens
        parent = FNV_SEED
        for i in range(0, len(toks), bt):
            parent = self.pool.publish(blocks[i // bt], parent,
                                       toks[i:i + bt])

    def _ensure_writable(self, slot_blocks: list, j: int) -> bool:
        """COW guard before writing into logical block ``j``: a shared
        or published block gets a private copy (pool ids + tensor rows)
        and the slot's list repoints.  True when a copy happened."""
        old = slot_blocks[j]
        new, copied = self.pool.cow(old)
        if copied:
            self.tree = self._copy_block_jit(
                self.tree, jnp.int32(old), jnp.int32(new))
            slot_blocks[j] = new
        return copied

    # -- the interface ---------------------------------------------------
    def admit(self, params, slot: int, toks: list, max_new: int):
        """Enqueue the prefill of ``toks`` into ``slot``'s blocks: the
        first token, on the device.  The program takes the pool donated,
        so it runs behind the decode step in flight; that step has its
        own copies of the tables and cursors pointed at here."""
        bt = self.cfg.block_tokens
        hits, pos = self._lookup_prefix(toks)
        new = len(toks) - pos          # what the prefix cache lacks
        if pos >= len(toks):
            # Whole prompt resident: no prefill, just the last prompt
            # token again for the next-token logits (its K/V rewrite is
            # value-identical; COW below keeps shared blocks untouched).
            pos = len(toks) - 1
            self.stats["prefill_skipped"] += 1
        blocks = self._block_run(slot, toks, max_new, hits)
        self._ensure_writable(blocks, pos // bt)
        self._count_prefill(new, prompt_bucket(self.cfg, len(toks) - pos))
        with span("serve.prefill_dispatch"):
            first = self._prefill(params, toks, blocks, pos)
        # The program wrote the pool rows itself; the host's part of the
        # insert: publish the blocks, point the slot's table at them.
        with span("serve.cache_insert"):
            self._publish_prompt(toks, blocks)
            self._point(slot, blocks)
        return first

    def _block_run(self, slot: int, toks: list, max_new: int,
                   hits: list) -> list:
        """The sequence's full block run: ``hits``, then fresh blocks."""
        total = -(-(len(toks) + max_new) // self.cfg.block_tokens)
        fresh = self.pool.alloc(total - len(hits))
        if fresh is None:
            # The front end reserves worst-case blocks per admission, so
            # this is unreachable unless accounting drifted; fail loud.
            for b in hits:
                self.pool.deref(b)
            raise RuntimeError(
                f"KV pool exhausted admitting into slot {slot}: "
                f"{self.pool.free_count()} free of {self.pool.num_blocks}")
        return hits + fresh

    def _point(self, slot: int, blocks: list) -> None:
        self._blocks[slot] = blocks
        self._tables[slot] = self._row(blocks)

    def decode(self, params, last_tokens: np.ndarray,
               from_host: np.ndarray, active: list, slots: list):
        bt = self.cfg.block_tokens
        for i in active:
            j = slots[i].seq_len // bt
            # COW guard: the write position may sit in a published tail
            # (the first divergent write of a shared prefix).
            if self._ensure_writable(self._blocks[i], j):
                self._tables[i][j] = self._blocks[i][j]
            self._cursors[i] = slots[i].seq_len
        return super().decode(params, last_tokens, from_host, active, slots)

    def release(self, slot: int) -> None:
        for b in self._blocks[slot]:
            self.pool.deref(b)
        self._point(slot, [])
        self._cursors[slot] = 0

    def kv_stats(self) -> dict:
        """The pool's residency and reuse, for reports and the census."""
        pool = self.pool
        return {"pool_blocks": pool.num_blocks,
                "block_tokens": pool.block_tokens,
                "free": pool.free_count(), "active": pool.active_count(),
                "cached": pool.cached_count(),
                "prefix_hits": pool._m_hits.value,
                "prefix_misses": pool._m_misses.value,
                "evictions": pool._m_evicted.value,
                "cow_copies": pool._m_cow.value}

    def close(self) -> None:
        """The pool must not outlive the executor (hvdlife HVD702/704)."""
        self.pool.close()

    # -- disaggregated prefill (serving/kvstream.py) ---------------------
    def holds_prompt(self, toks: list) -> bool:
        """Whether the prefix cache covers the whole prompt (no block
        stays referenced: ``admit`` looks the prompt up again)."""
        hits, pos = self._lookup_prefix(toks)
        for b in hits:
            self.pool.deref(b)
        return pos >= len(toks)

    def prefill_image(self, params, toks: list) -> tuple[int, np.ndarray]:
        """Prefill-rank half: the prompt's blocks computed in the local
        scratch pool (identity table): (first token, the pool rows across
        every layer [n_leaves, nblk, bt, H, D], ready to serialize)."""
        nblk = -(-len(toks) // self.cfg.block_tokens)
        first = self._prefill(params, toks, list(range(nblk)), 0)
        return int(first), np.stack(
            [np.asarray(node[key][:nblk])
             for key, node in self.family.paged_pool_leaves(self.tree)])

    def land(self, slot: int, toks: list, max_new: int,
             image: np.ndarray) -> None:
        """A streamed prefill into the pool: allocate the sequence's
        block run, write the prompt rows, publish them, point the table."""
        blocks = self._block_run(slot, toks, max_new, [])
        idx = jnp.asarray(np.asarray(blocks[:image.shape[1]], np.int32))
        for i, (key, node) in enumerate(
                self.family.paged_pool_leaves(self.tree)):
            node[key] = node[key].at[idx].set(jnp.asarray(image[i]))
        self._publish_prompt(toks, blocks)
        self._point(slot, blocks)
