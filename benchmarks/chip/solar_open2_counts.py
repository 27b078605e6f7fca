"""Operation and byte counts of ``Solar-Open2-250B`` as one chip of its
deployment holds it, from shapes alone (``counts.py`` says what such
counts are: what the algorithm needs, never what a compiler emitted).
Every function takes ``(config, contexts)``, the live contexts of the
slots that decode in one step.

Under even routing each of a step's tokens takes
``num_experts_per_tok`` of the router's ``router_experts`` with equal
chance, so of the ``n_routed_experts`` held here a step of ``n`` tokens
touches ``held * (1 - (1 - k / E)^n)`` a layer (34.7 of 40 at 80
tokens), and ``n * k * held / E`` token-expert pairs are computed here.
That is what a whole step's counts reckon with (``decode_bytes_per_step``,
``decode_flops_per_step``): shapes know no better.  Under the seeded
weights the tokens of a step agree more than chance would have them and
touch 74 to 79% of the held experts, not 86.8% (``PERF.md``, PR 34), so
the whole step's bytes are reckoned some 5% high.  The expert kernel's
own share of its roofline does not take the formula: the program counts
the experts it touched and the pairs it computed in the same window
(``stats.moe_experts_touched``, ``stats.moe_local_pairs``), and
``kernels.moe_experts_roofline`` multiplies the two counts below, what a
step would move if every held expert were touched and every routed pair
were local, by those shares."""
from __future__ import annotations

import counts

STATE_BYTES = 4          # the delta-rule state is float32, by the program
COMBINE_BYTES = 4        # an expert's output row, summed in float32


def layers(cfg: dict) -> tuple[int, int]:
    """(KDA layers, attention layers)."""
    delta = sum(kind == "kda" for kind in cfg["layer_types"])
    return delta, len(cfg["layer_types"]) - delta


def kda_sizes(cfg: dict) -> tuple[int, int, int]:
    """(heads, a head's width, numbers of one layer's state a slot)."""
    own = cfg["linear_attn_config"]
    heads, width = own["num_heads"], own["head_dim"]
    return heads, width, heads * width * width


def expert_params(cfg: dict) -> int:
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def experts_touched(cfg: dict, tokens: int) -> float:
    """Held experts a layer that a step of ``tokens`` touches, under
    even routing."""
    miss = 1.0 - cfg["num_experts_per_tok"] / cfg["router_experts"]
    return cfg["n_routed_experts"] * (1.0 - miss ** tokens)


def local_pairs(cfg: dict, tokens: int) -> float:
    """Token-expert pairs a layer computed here, under even routing."""
    return tokens * cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["router_experts"]


def dense_params(cfg: dict) -> int:
    """Weights that multiply every token whatever the router says: a KDA
    layer's projections (q, k, v, the two low-rank pairs, the write
    strength, the output), the attention layer's five (grouped keys and
    values, the gate), the router and the shared expert in every layer,
    and the head's slice once (the embedding is a gather)."""
    d = cfg["hidden_size"]
    heads, width, _ = kda_sizes(cfg)
    inner, rank = heads * width, cfg["kda_gate_rank"]
    q_width = cfg["num_attention_heads"] * cfg["head_dim"]
    kv_width = cfg["num_key_value_heads"] * cfg["head_dim"]
    delta, attention = layers(cfg)
    return (delta * (d * (3 * inner + 2 * rank + heads) + 2 * rank * inner
                     + inner * d)
            + attention * (3 * d * q_width + 2 * d * kv_width)
            + (delta + attention) * (
                d * cfg["router_experts"]
                + cfg["n_shared_experts"] * expert_params(cfg))
            + d * cfg["vocab_size"])


def kda_update_bytes_per_step(cfg: dict, contexts: list[int]) -> int:
    """What ``hvd.kda_update`` alone must move in a step, over all KDA
    layers: each decoding slot's state read and written in float32, its
    small operands (decay, key and query a key channel, the value, the
    write strength a head) and its output, float32 too."""
    heads, width, state = kda_sizes(cfg)
    a_slot = 2 * state + 5 * heads * width + heads
    return int(layers(cfg)[0] * len(contexts) * a_slot * STATE_BYTES)


def moe_held_expert_bytes_per_step(cfg: dict, contexts: list[int]) -> int:
    """The three matrices of every expert held, over all layers: what
    ``hvd.moe_experts`` would read of weights in a step that touched
    them all.  The kernel's roofline takes the share of them that the
    program counted as touched."""
    return len(cfg["layer_types"]) * cfg["n_routed_experts"] \
        * expert_params(cfg) * counts.dtype_bytes(cfg, "param_dtype")


def moe_routed_row_bytes_per_step(cfg: dict, contexts: list[int]) -> int:
    """The input row read and the float32 output row written of every
    token-expert pair a step routes, over all layers, wherever its
    expert lives.  The kernel's roofline takes the share of the pairs
    that the program counted as computed here."""
    return len(cfg["layer_types"]) * len(contexts) \
        * cfg["num_experts_per_tok"] * cfg["hidden_size"] \
        * (counts.dtype_bytes(cfg, "dtype") + COMBINE_BYTES)


def moe_expert_bytes_per_step(cfg: dict, contexts: list[int]) -> int:
    """What ``hvd.moe_experts`` must move in a step under even routing,
    over all layers (the whole step's count, and the predictions): the
    three matrices of every held expert that the step's tokens touch
    (``experts_touched``, not all that are held), and for each pair
    computed here its input row read and its output row written."""
    tokens = len(contexts)
    param, act = counts.dtype_bytes(cfg, "param_dtype"), \
        counts.dtype_bytes(cfg, "dtype")
    a_layer = experts_touched(cfg, tokens) * expert_params(cfg) * param \
        + local_pairs(cfg, tokens) * cfg["hidden_size"] \
        * (act + COMBINE_BYTES)
    return int(len(cfg["layer_types"]) * a_layer)


def decode_bytes_per_step(cfg: dict, contexts: list[int]) -> int:
    """Bytes one decode step has to move: every dense weight once and an
    embedding row a slot; the touched experts' weights and their pairs'
    rows; each slot's delta-rule state read and written and its
    convolution windows read and written, in every KDA layer, whatever
    the context; the keys and values of each slot's live context (not of
    ``max_seq``) in the attention layer, and the one new key and value
    written."""
    slots = len(contexts)
    param, act = counts.dtype_bytes(cfg, "param_dtype"), \
        counts.dtype_bytes(cfg, "dtype")
    heads, width, state = kda_sizes(cfg)
    delta, attention = layers(cfg)
    weights = (dense_params(cfg) + slots * cfg["hidden_size"]) * param
    window = (cfg["linear_attn_config"]["short_conv_kernel_size"] - 1) \
        * 3 * heads * width
    recurrent = slots * delta * 2 * (state * STATE_BYTES + window * act)
    kv = (sum(contexts) + slots) * attention * 2 \
        * cfg["num_key_value_heads"] * cfg["head_dim"] * act
    return weights + moe_expert_bytes_per_step(cfg, contexts) + recurrent + kv


def decode_flops_per_step(cfg: dict, contexts: list[int]) -> int:
    """Operations one decode step needs: 2 a dense weight for each
    slot's one token and 2 an expert's weight for each pair computed
    here; the state update's 8 a number of state (forget, read, correct,
    read out) a slot and KDA layer; scores and values over the live
    context, 2 * 2 * context * query width, in the attention layer."""
    slots = len(contexts)
    delta, attention = layers(cfg)
    return int(2 * dense_params(cfg) * slots
               + 2 * expert_params(cfg) * local_pairs(cfg, slots)
               * len(cfg["layer_types"])
               + 8 * kda_sizes(cfg)[2] * delta * slots
               + 4 * cfg["num_attention_heads"] * cfg["head_dim"]
               * attention * sum(contexts))
