"""The stub architecture's check against its plain reference, named by
its configuration (``"reference": "stub_reference:check"``).  It owns its
sizes and its tolerance; here it borrows the decoder's, since the stub
runs the decoder."""
from __future__ import annotations

import reference


def check(run, cfg: dict) -> dict:
    seen = reference.check_serve(run, cfg)
    seen.update(tolerance=0.03, reference="stub_reference:check")
    return seen
