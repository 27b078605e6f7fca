"""What the serving replica asks of a model family.

``serving/replica.py`` serves whatever configuration object it is handed
(``ServeConfig.model_cfg``).  The configuration says which family it is
through its ``family`` property, a ``ModelFamily``: how to build the
model, a fresh slot cache, ``prefill`` and ``decode_step``, and what one
generated token costs.  Besides that the replica needs two fields of the
configuration itself, ``decode`` and ``max_seq_len`` (it sets both with
``dataclasses.replace``).  ``models/kvcache.py`` has the entry points
and the attention over the cache: a new family writes no cache code.

Every leaf of a family's cache has the slot on axis 0: the replica's
slot cache (``serving/slotcache.py``) inserts a prefilled request as row
``slot`` of every leaf, and donates the whole tree to the decode program.

``models/transformer.py`` (``TransformerLM``) is the first family,
``models/hybrid.py`` (``HybridLM``) the second; with routed experts its
decode step also counts what only the device knows (``decode_counters``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable


@dataclasses.dataclass(frozen=True)
class ModelFamily:
    name: str
    # (config) -> the flax module
    build: Callable[[Any], Any]
    # (model, params, slots) -> a cache of ``slots`` empty rows
    fresh_cache: Callable[[Any, Any, int], dict]
    # (model, variables, tokens [B, T], lengths) -> (logits, cache of B rows)
    prefill: Callable[..., tuple]
    # (model, variables, cache, tokens [B, 1]) -> (logits, cache); a
    # dict given as ``sown`` receives what the layers sowed into the
    # collections it names (models/kvcache.py:_apply)
    decode_step: Callable[..., tuple]
    # (config, context) -> operations of one generated token
    decode_flops: Callable[[Any, float], float]
    # Names of the cache leaves that hold recurrent state (a fixed size a
    # slot, live until replaced) and not keys and values.
    state_leaves: tuple = ()
    # Names of what a decode step counts on the device (the model sows
    # each into its "counters" collection, a layer at a time): the sums
    # over the layers ride back behind the slots' tokens, in the same
    # fetch, and add up in the executor's ``stats`` under these names.
    decode_counters: tuple = ()
    # Why the paged cache (serving/kvpool.py) cannot hold this family
    # yet; empty where it can, and then the three below are given
    # (models/kvcache.py says what each takes and returns).
    paged_missing: str = ""
    paged_apply: Callable[..., tuple] | None = None
    paged_copy_block: Callable[..., dict] | None = None
    paged_pool_leaves: Callable[[dict], list] | None = None
