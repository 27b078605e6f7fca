"""Operation and byte counts of ``MiMo-V2.5`` as one chip of its
deployment holds it, from shapes alone (``counts.py`` says what such
counts are: what the algorithm needs, never what a compiler emitted nor
what a leaf pads).  Every function takes ``(config, contexts)``, the
live contexts of the slots that decode in one step.

A global layer reads every live position of a slot, a window layer the
last ``sliding_window`` of them.  The whole step's counts reckon even
routing, as ``solar_open2_counts.py`` does and for its reason (shapes
know no better); the expert kernel's own share of its roofline takes
the program's counters of the window laid on the two ``moe_*`` counts
below."""
from __future__ import annotations

import counts
import solar_open2_counts as routed

OUT_BYTES = 4            # an attention output row leaves the kernel float32


def layers(cfg: dict) -> tuple[int, int, int]:
    """(global layers, window layers, layers with the expert block)."""
    window = sum(kind == "window" for kind in cfg["layer_types"])
    return len(cfg["layer_types"]) - window, window, \
        len(cfg["layer_types"]) - len(cfg["dense_layers"])


def attention_params(cfg: dict, kv_heads: int) -> int:
    """One attention layer's four projections at ``kv_heads``."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return d * (heads + kv_heads) * cfg["head_dim"] \
        + d * kv_heads * cfg["v_head_dim"] + heads * cfg["v_head_dim"] * d


def dense_params(cfg: dict) -> int:
    """Weights that multiply every token whatever the router says: the
    attention projections of every layer, the dense MLP where a layer
    has one, the routers, and the head's slice once (the embedding is a
    gather)."""
    d = cfg["hidden_size"]
    full, window, sparse = layers(cfg)
    return (full * attention_params(cfg, cfg["num_key_value_heads"])
            + window * attention_params(cfg, cfg["swa_num_key_value_heads"])
            + len(cfg["dense_layers"]) * 3 * d * cfg["intermediate_size"]
            + sparse * d * cfg["router_experts"] + d * cfg["vocab_size"])


def row_width(cfg: dict, kv_heads: int) -> int:
    """Numbers of one position's keys and values in one layer."""
    return kv_heads * (cfg["head_dim"] + cfg["v_head_dim"])


def decode_attend_bytes_per_step(cfg: dict, contexts: list[int]) -> int:
    """What ``hvd.decode_attend`` alone must move in a step, over the
    global layers: each live position's keys and values at the published
    widths (this step's own among them), each slot's queries in, and its
    outputs, float32, out."""
    act = counts.dtype_bytes(cfg, "dtype")
    heads = cfg["num_attention_heads"]
    slots = len(contexts)
    live = (sum(contexts) + slots) \
        * row_width(cfg, cfg["num_key_value_heads"]) * act
    ends = slots * heads * (cfg["head_dim"] * act
                            + cfg["v_head_dim"] * OUT_BYTES)
    return layers(cfg)[0] * (live + ends)


def moe_held_expert_bytes_per_step(cfg: dict, contexts: list[int]) -> int:
    """The three matrices of every expert held, over the layers that
    have experts: what ``hvd.moe_experts`` would read of weights in a
    step that touched them all."""
    return layers(cfg)[2] * cfg["n_routed_experts"] \
        * routed.expert_params(cfg) * counts.dtype_bytes(cfg, "param_dtype")


def moe_routed_row_bytes_per_step(cfg: dict, contexts: list[int]) -> int:
    """The input row read and the float32 output row written of every
    token-expert pair a step routes, over the layers that have experts,
    wherever its expert lives."""
    return layers(cfg)[2] * len(contexts) * cfg["num_experts_per_tok"] \
        * cfg["hidden_size"] * (counts.dtype_bytes(cfg, "dtype")
                                + routed.COMBINE_BYTES)


def decode_bytes_per_step(cfg: dict, contexts: list[int]) -> int:
    """Bytes one decode step has to move: every dense weight once and an
    embedding row a slot; under even routing the touched experts'
    weights and the rows of the pairs computed here; the keys and values
    of each slot's live context (not of ``max_seq``) in the global
    layers and of its last ``sliding_window`` positions in the window
    layers, and the new key and value written in each."""
    slots = len(contexts)
    param, act = counts.dtype_bytes(cfg, "param_dtype"), \
        counts.dtype_bytes(cfg, "dtype")
    full, window, sparse = layers(cfg)
    weights = (dense_params(cfg) + slots * cfg["hidden_size"]) * param
    experts = sparse * (
        routed.experts_touched(cfg, slots) * routed.expert_params(cfg) * param
        + routed.local_pairs(cfg, slots) * cfg["hidden_size"]
        * (act + routed.COMBINE_BYTES))
    ring = sum(min(c, cfg["sliding_window"]) for c in contexts)
    kv = full * (sum(contexts) + slots) \
        * row_width(cfg, cfg["num_key_value_heads"]) * act \
        + window * (ring + slots) \
        * row_width(cfg, cfg["swa_num_key_value_heads"]) * act
    return int(weights + experts + kv)


def decode_flops_per_step(cfg: dict, contexts: list[int]) -> int:
    """Operations one decode step needs: 2 a dense weight for each
    slot's one token and 2 an expert's weight for each pair computed
    here; scores over ``head_dim`` and values over ``v_head_dim``, 2
    each a query head and visible position."""
    slots = len(contexts)
    full, window, sparse = layers(cfg)
    ring = sum(min(c, cfg["sliding_window"]) for c in contexts)
    seen = 2 * cfg["num_attention_heads"] \
        * (cfg["head_dim"] + cfg["v_head_dim"])
    return int(2 * dense_params(cfg) * slots
               + 2 * routed.expert_params(cfg)
               * routed.local_pairs(cfg, slots) * sparse
               + seen * (full * sum(contexts) + window * ring))
