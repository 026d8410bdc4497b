"""What a driver records of a window, for the metric readers and the
check: one entry per request and per step, on the host's monotonic clock."""
from __future__ import annotations

from dataclasses import dataclass, field


@dataclass
class Tracked:
    """One request as the benchmark saw it (host monotonic seconds)."""
    rid: int
    prompt_len: int
    max_new: int
    due: float
    submit: float
    req: object = None               # the program's Request
    admit: float | None = None       # start of the step_once that gave it a slot
    first: float | None = None       # end of the step that brought token 1
    last: float | None = None        # end of the step that brought its last
    n_out: int = 0


@dataclass
class Step:
    start: float
    end: float
    decode: bool                     # a decode step ran in this step_once
    work: list = field(default_factory=list)   # [(cache length, tokens)] per slot


@dataclass
class Window:
    start: float
    end: float                       # end of the window proper
    drained: float                   # end of the drain (open loop)
    steps: list
    tracked: list
    late_s: list                     # submit time minus due time, open loop
    step_failures: int
