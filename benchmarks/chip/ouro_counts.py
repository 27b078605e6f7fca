"""Operation and byte counts of ``Ouro-2.6B``, a looped model, from shapes
alone (``counts.py`` says what such counts are: what the algorithm needs,
never what a compiler emitted nor what a leaf pads).  Every function
takes ``(config, contexts)``, the live contexts of the slots that decode
in one step.

One decode step runs the stack ``total_ut_steps`` times: it meets the 48
layers' weights once a pass (4 x 4.93 GB at the published widths) and
the head once, and every pass attends over a cache of its own, so a live
position is read in ``passes x layers`` = 192 attention layers, 8,192
bytes each.  At 2 x 2 x 16 x 128 = 8,192 operations a position and a
layer, **one operation a byte at any context**: ``hvd.decode_attend`` is
bound by HBM, as the whole step is."""
from __future__ import annotations

import counts

OUT_BYTES = 4            # an attention output row leaves the kernel float32


def passes(cfg: dict) -> int:
    return cfg["total_ut_steps"]


def attention_layers(cfg: dict) -> int:
    """(pass, layer) pairs: each keeps its own keys and values."""
    return passes(cfg) * cfg["num_hidden_layers"]


def row_width(cfg: dict) -> int:
    """Numbers of one position's keys and values in one attention layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"]


def layer_matmul_params(cfg: dict) -> int:
    """One layer's projections and gated MLP (51,380,224 at the published
    widths); its four norms are elementwise."""
    d, width = cfg["hidden_size"], cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return d * (2 * width + 2 * kv) + 3 * d * cfg["intermediate_size"]


def layer_params(cfg: dict) -> int:
    """One layer's weights, its four norms among them (51,388,416)."""
    return layer_matmul_params(cfg) + 4 * cfg["hidden_size"]


def params(cfg: dict) -> int:
    """The whole model: the 48 layers, the embedding, the untied head and
    the final norm (2,667,972,608 at the published widths)."""
    d = cfg["hidden_size"]
    return cfg["num_hidden_layers"] * layer_params(cfg) \
        + 2 * d * cfg["vocab_size"] + d


def decode_attend_bytes_per_step(cfg: dict, contexts: list[int]) -> int:
    """What ``hvd.decode_attend`` alone must move in a step, over the 192
    (pass, layer) caches: each live position's keys and values (this
    step's own among them), each slot's queries in and its outputs,
    float32, out; whatever a leaf pads."""
    act = counts.dtype_bytes(cfg, "dtype")
    heads, slots = cfg["num_attention_heads"], len(contexts)
    live = (sum(contexts) + slots) * row_width(cfg) * act
    ends = slots * heads * cfg["head_dim"] * (act + OUT_BYTES)
    return attention_layers(cfg) * (live + ends)


def decode_bytes_per_step(cfg: dict, contexts: list[int]) -> int:
    """Bytes one decode step has to move: every layer's weights once a
    pass, the head and the final norm once (a norm's scale once a pass),
    an embedding row a slot; each slot's live keys and values (not
    ``max_seq``'s) in every (pass, layer) cache, and the new row written
    in each."""
    slots = len(contexts)
    param, act = counts.dtype_bytes(cfg, "param_dtype"), \
        counts.dtype_bytes(cfg, "dtype")
    d = cfg["hidden_size"]
    weights = passes(cfg) * (cfg["num_hidden_layers"] * layer_params(cfg)
                             + d) + d * cfg["vocab_size"] + slots * d
    rows = attention_layers(cfg) * (sum(contexts) + slots) \
        * row_width(cfg) * act
    return int(weights * param + rows)


def decode_flops_per_step(cfg: dict, contexts: list[int]) -> int:
    """Operations one decode step needs: 2 a layer's matmul weight for
    each slot's token in every pass and 2 a head weight once; scores
    and values over the live context, 2 x 2 x heads x head_dim a
    position, in every (pass, layer) cache."""
    slots = len(contexts)
    d = cfg["hidden_size"]
    attend = 4 * cfg["num_attention_heads"] * cfg["head_dim"]
    return int(2 * slots * (passes(cfg) * cfg["num_hidden_layers"]
                            * layer_matmul_params(cfg)
                            + d * cfg["vocab_size"])
               + attend * attention_layers(cfg) * sum(contexts))
