"""Operation and byte counts, from shapes alone, and the table of peaks.

The yardstick of every utilization and roofline share the benchmark
reports: what the algorithm needs, never what a compiler happened to
emit.  Recomputed operations are not counted.  A configuration names the
function it is counted by (``"counts": {"flops_per_item":
"counts:transformer_train_flops"}``), so a later architecture brings its
own module beside this one and edits nothing here.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The peaks of one device kind; a device that is not in the table is
    an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"peaks.json has no device kind {device_kind!r}")
    return table[device_kind]


def transformer_matmul_params(cfg: dict) -> int:
    """Weights that multiply every token: four attention projections and
    the gated MLP's three matrices a layer, and the output head.  The
    embedding is a gather and multiplies nothing."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    layer = 4 * d * d + 3 * d * ff
    return cfg["num_hidden_layers"] * layer + d * cfg["vocab_size"]


def transformer_train_flops(cfg: dict, traffic: dict) -> int:
    """Forward plus backward operations of one token of a causal
    sequence of ``seq_len``: 6 a matmul weight (2 forward, 4 backward),
    and scores and values over half the square, 2 * 2 * seq * d / 2
    forward and twice that backward, a layer."""
    seq, d = traffic["seq_len"], cfg["hidden_size"]
    return (6 * transformer_matmul_params(cfg)
            + cfg["num_hidden_layers"] * 6 * seq * d)


DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def dtype_bytes(cfg: dict, arg: str) -> int:
    """Bytes an element of the model argument ``arg`` of the
    configuration (``"@jax.numpy:bfloat16"``); a type that is not in the
    table is an error, never a default."""
    return DTYPE_BYTES[cfg["model"]["args"][arg].rpartition(":")[2]]


def transformer_decode_bytes(cfg: dict, contexts: list[int]) -> int:
    """Bytes one decode step has to move for slots whose live contexts
    are ``contexts``: every matmul weight once and one embedding row a
    slot, in the configuration's ``param_dtype``, and the keys and values
    of each slot's live context (not of ``max_seq``) in every layer, in
    its ``dtype`` (the program's cache takes the activations' type)."""
    d = cfg["hidden_size"]
    weights = (transformer_matmul_params(cfg) + len(contexts) * d) \
        * dtype_bytes(cfg, "param_dtype")
    kv = sum(contexts) * 2 * cfg["num_hidden_layers"] * d \
        * dtype_bytes(cfg, "dtype")
    return weights + kv


def transformer_decode_flops(cfg: dict, contexts: list[int]) -> int:
    """Operations one decode step needs for slots whose live contexts
    are ``contexts``: 2 a matmul weight for each slot's one token, and
    scores and values over the slot's live context, 2 * 2 * context * d
    a layer."""
    d = cfg["hidden_size"]
    return (2 * transformer_matmul_params(cfg) * len(contexts)
            + 4 * d * cfg["num_hidden_layers"] * sum(contexts))
