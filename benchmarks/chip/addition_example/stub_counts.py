"""The stub architecture's counts.  Every key of a configuration's
``counts`` that ends in ``_per_step`` is evaluated with ``(config,
contexts)`` on the traced steps that admit nothing, and its mean is a
counter of that key."""
from __future__ import annotations

import counts


def decode_bytes(cfg: dict, contexts: list[int]) -> int:
    return counts.transformer_decode_bytes(cfg, contexts)


def state_bytes(cfg: dict, contexts: list[int]) -> int:
    """A recurrent state's bytes a step: a fixed size a slot, whatever
    its context (the number is the stub's own)."""
    return len(contexts) * cfg["state_numbers_per_slot"] * 4
