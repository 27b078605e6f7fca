"""The stub architecture's configuration class: the program's own with a
field the program does not know, as a new architecture's would have."""
from __future__ import annotations

import dataclasses

from horovod_tpu.models import TransformerConfig


@dataclasses.dataclass(frozen=True)
class StubConfig(TransformerConfig):
    residual_multiplier: float = 1.0
