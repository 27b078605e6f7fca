"""Operation and byte counts of ``granite-4.0-h-micro``, from shapes alone
(``counts.py`` says what such counts are: what the algorithm needs, never
what a compiler emitted).  Every function takes ``(config, contexts)``,
the live contexts of the slots that decode in one step."""
from __future__ import annotations

import counts

STATE_BYTES = 4          # the recurrent state is float32, by the program


def layers(cfg: dict) -> tuple[int, int]:
    """(Mamba layers, attention layers)."""
    mamba = sum(kind == "mamba" for kind in cfg["layer_types"])
    return mamba, len(cfg["layer_types"]) - mamba


def mamba_sizes(cfg: dict) -> tuple[int, int, int]:
    """(inner channels, convolution channels, numbers of one state)."""
    inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    return (inner, inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"],
            inner * cfg["mamba_d_state"])


def kv_width(cfg: dict) -> int:
    return cfg["num_key_value_heads"] \
        * (cfg["hidden_size"] // cfg["num_attention_heads"])


def matmul_params(cfg: dict) -> int:
    """Weights that multiply every token: a Mamba layer's two projections,
    an attention layer's four (grouped keys and values), the gated MLP's
    three matrices in every layer, and the tied matrix once, as the head
    (as the embedding it is a gather)."""
    d, ff = cfg["hidden_size"], cfg["shared_intermediate_size"]
    inner, channels, _ = mamba_sizes(cfg)
    mamba, attention = layers(cfg)
    return (mamba * (d * (inner + channels + cfg["mamba_n_heads"])
                     + inner * d)
            + attention * (2 * d * d + 2 * d * kv_width(cfg))
            + (mamba + attention) * 3 * d * ff + d * cfg["vocab_size"])


def ssm_update_bytes_per_step(cfg: dict, contexts: list[int]) -> int:
    """What ``hvd.ssm_update`` alone must move in a step, over all Mamba
    layers: each decoding slot's state read and written in float32, its
    small inputs (x, dt, B, C) and its output y, also float32, and A and
    D once."""
    heads, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], \
        cfg["mamba_d_state"]
    a_slot = 2 * heads * p * n + 2 * heads * p + heads + 2 * n
    return layers(cfg)[0] * (len(contexts) * a_slot + 2 * heads) \
        * STATE_BYTES


def decode_bytes_per_step(cfg: dict, contexts: list[int]) -> int:
    """Bytes one decode step has to move: every matmul weight once and an
    embedding row a slot; each slot's recurrent state read and written
    and its convolution windows read and written, in every Mamba layer,
    whatever the context; the keys and values of each slot's live
    context (not of ``max_seq``) in the attention layers, and the one
    new key and value written."""
    slots = len(contexts)
    param, act = counts.dtype_bytes(cfg, "param_dtype"), \
        counts.dtype_bytes(cfg, "dtype")
    _, channels, state = mamba_sizes(cfg)
    mamba, attention = layers(cfg)
    weights = (matmul_params(cfg) + slots * cfg["hidden_size"]) * param
    recurrent = slots * mamba * 2 * (
        state * STATE_BYTES + (cfg["mamba_d_conv"] - 1) * channels * act)
    kv = (sum(contexts) + slots) * attention * 2 * kv_width(cfg) * act
    return weights + recurrent + kv


def decode_flops_per_step(cfg: dict, contexts: list[int]) -> int:
    """Operations one decode step needs: 2 a matmul weight for each
    slot's one token; the state update's 6 a number of state (decay,
    outer product and sum, read-out) a slot and Mamba layer; scores and
    values over the live context, 2 * 2 * context * hidden, in the
    attention layers."""
    mamba, attention = layers(cfg)
    return (2 * matmul_params(cfg) * len(contexts)
            + 6 * mamba_sizes(cfg)[2] * mamba * len(contexts)
            + 4 * cfg["hidden_size"] * attention * sum(contexts))
