"""What one measured window leaves for the metrics and the check."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Record:
    window_s: float = 0.0
    attempted: int = 0
    completed: int = 0
    #: completed by the optimized path (not a fallback)
    optimized: int = 0
    failed: int = 0
    #: seconds per request, in completion order
    latencies: list = dataclasses.field(default_factory=list)
    #: seconds each request was sent after it was due (open loop)
    lateness: list = dataclasses.field(default_factory=list)
    #: offline batches: index, prompt length, start/end, served tokens
    batches: list = dataclasses.field(default_factory=list)
    prompt_tokens: int = 0
    new_tokens: int = 0
    #: request index -> outputs kept for the check (a seeded sample)
    kept: dict = dataclasses.field(default_factory=dict)
