"""Operation and byte counts of ``A.X-K1`` as one chip of its deployment
holds it, from shapes alone (``counts.py`` says what such counts are:
what the algorithm needs, never what a compiler emitted nor what a leaf
pads).  Every function takes ``(config, contexts)``, the live contexts
of the slots that decode in one step.

Every layer is a latent layer (multi-head latent attention, decoded in
the absorbed form, ``horovod_tpu/ops/mla.py``): a position is one row of
``kv_lora_rank + qk_rope_head_dim`` numbers a layer (576, 1,152 bytes),
which every head reads, 64 heads x (576 + 512) x 2 = 139,264 operations
a position and a slot: **121 operations a byte at any context**, under
the v5e's ridge of 240, so ``hvd.mla_decode`` is bound by HBM.  The
whole step's counts reckon even routing, as ``solar_open2_counts.py``
does and for its reason; the expert kernel's own share of its roofline
takes the program's counters of the window laid on the two ``moe_*``
counts below."""
from __future__ import annotations

import counts
import solar_open2_counts as routed

OUT_BYTES = 4           # the weighted latent leaves the kernel float32


def layers(cfg: dict) -> tuple[int, int]:
    """(latent layers, layers with the expert block)."""
    every = len(cfg["layer_types"])
    return sum(kind == "latent" for kind in cfg["layer_types"]), \
        every - len(cfg["dense_layers"])


def row_width(cfg: dict) -> int:
    """Numbers of one position's latent row in one layer."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def attention_params(cfg: dict) -> int:
    """One latent layer's projections: ``W_qa``, ``W_qb``, ``W_kva``,
    ``W_kvb`` and ``W_o`` (101.1 M at the published widths)."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    q_rank, rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, wide = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    return d * q_rank + q_rank * heads * (nope + rope) + d * (rank + rope) \
        + rank * heads * (nope + wide) + heads * wide * d


def dense_params(cfg: dict) -> int:
    """Weights that multiply every token whatever the router says: every
    layer's attention projections, the dense MLP where a layer has one,
    the routers and the shared experts of the others, and the head's
    slice once (the embedding is a gather)."""
    d = cfg["hidden_size"]
    latent, sparse = layers(cfg)
    return (latent * attention_params(cfg)
            + len(cfg["dense_layers"]) * 3 * d * cfg["intermediate_size"]
            + sparse * (d * cfg["router_experts"]
                        + cfg["n_shared_experts"] * routed.expert_params(cfg))
            + d * cfg["vocab_size"])


def mla_decode_bytes_per_step(cfg: dict, contexts: list[int]) -> int:
    """What ``hvd.mla_decode`` alone must move in a step, over the latent
    layers: each live position's row at the published widths (this
    step's own among them), each slot's 64 absorbed queries in and its
    weighted latents, float32, out; whatever the leaf pads."""
    act = counts.dtype_bytes(cfg, "dtype")
    heads, slots = cfg["num_attention_heads"], len(contexts)
    live = (sum(contexts) + slots) * row_width(cfg) * act
    ends = slots * heads * (row_width(cfg) * act
                            + cfg["kv_lora_rank"] * OUT_BYTES)
    return layers(cfg)[0] * (live + ends)


def moe_held_expert_bytes_per_step(cfg: dict, contexts: list[int]) -> int:
    """The three matrices of every expert held, over the layers that
    have experts: what ``hvd.moe_experts`` would read of weights in a
    step that touched them all."""
    return layers(cfg)[1] * cfg["n_routed_experts"] \
        * routed.expert_params(cfg) * counts.dtype_bytes(cfg, "param_dtype")


def moe_routed_row_bytes_per_step(cfg: dict, contexts: list[int]) -> int:
    """The input row read and the float32 output row written of every
    token-expert pair a step routes, over the layers that have experts,
    wherever its expert lives."""
    return layers(cfg)[1] * len(contexts) * cfg["num_experts_per_tok"] \
        * cfg["hidden_size"] * (counts.dtype_bytes(cfg, "dtype")
                                + routed.COMBINE_BYTES)


def decode_bytes_per_step(cfg: dict, contexts: list[int]) -> int:
    """Bytes one decode step has to move: every dense weight once and an
    embedding row a slot; under even routing the touched experts'
    weights and the rows of the pairs computed here; each slot's live
    latent rows (not ``max_seq``'s) in every latent layer, with the new
    row written."""
    slots = len(contexts)
    param, act = counts.dtype_bytes(cfg, "param_dtype"), \
        counts.dtype_bytes(cfg, "dtype")
    latent, sparse = layers(cfg)
    weights = (dense_params(cfg) + slots * cfg["hidden_size"]) * param
    experts = sparse * (
        routed.experts_touched(cfg, slots) * routed.expert_params(cfg) * param
        + routed.local_pairs(cfg, slots) * cfg["hidden_size"]
        * (act + routed.COMBINE_BYTES))
    rows = latent * (sum(contexts) + 2 * slots) * row_width(cfg) * act
    return int(weights + experts + rows)


def decode_flops_per_step(cfg: dict, contexts: list[int]) -> int:
    """Operations one decode step needs: 2 a dense weight for each
    slot's one token and 2 an expert's weight for each pair computed
    here; in the absorbed form, scores over the latent row's 576 numbers
    and values over its 512, 2 each a query head and live position."""
    slots = len(contexts)
    latent, sparse = layers(cfg)
    seen = 2 * cfg["num_attention_heads"] \
        * (row_width(cfg) + cfg["kv_lora_rank"])
    return int(2 * dense_params(cfg) * slots
               + 2 * routed.expert_params(cfg)
               * routed.local_pairs(cfg, slots) * sparse
               + seen * latent * sum(contexts))
