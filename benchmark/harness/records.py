"""What a driver hands back from one run, and what the metric readers read.

A reader is ``read(records) -> number or None``. None means "nothing to read
here" and the harness leaves that metric out of the line."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional


@dataclass
class RequestRecord:
    due: float                      # host clock: when the schedule wanted it sent
    submitted: float                # host clock: when the generator sent it
    prompt_tokens: int
    output_tokens: int              # asked for
    in_window: bool = True          # due inside the measured window
    queue_s: Optional[float] = None  # the scheduler's own Request.queue_seconds
    first_token: Optional[float] = None
    token_times: List[float] = field(default_factory=list)  # one per emitted token
    finished: bool = False


@dataclass
class Records:
    cell: Any                       # manifest.Cell
    seed: int
    seconds: float
    peaks: Dict[str, Any]
    chips: int
    devices: List[Any] = field(default_factory=list)
    setup_s: float = 0.0
    window_open: float = 0.0        # host clock
    window_close: float = 0.0
    # serving: one entry per scheduler tick of the whole run (warm-up included)
    tick_end: List[float] = field(default_factory=list)
    tick_tokens: List[int] = field(default_factory=list)     # tokens emitted by the tick
    tick_parts: List[Dict[str, float]] = field(default_factory=list)  # seconds by what the host did
    tick_decoding: List[int] = field(default_factory=list)   # slots decoding in the tick
    tick_live_rows: List[int] = field(default_factory=list)  # cache rows holding a live token
    tick_admitted: List[int] = field(default_factory=list)
    tick_prefill_dispatches: List[int] = field(default_factory=list)
    slots: int = 0
    context: int = 0
    requests: List[RequestRecord] = field(default_factory=list)
    # training: one entry per step of the whole run (warm-up included)
    step_end: List[float] = field(default_factory=list)
    step_seconds: List[float] = field(default_factory=list)
    step_loss: List[float] = field(default_factory=list)
    tokens_per_step: int = 0
    compiles_in_window: int = 0
    trace: Any = None               # harness.trace.TraceSummary of the traced part, or None
    traced: Optional[tuple] = None  # (first, last) tick or step index inside the traced part
    attempted: int = 0
    failed: int = 0
    check: Dict[str, Any] = field(default_factory=dict)        # the comparison with the reference
    notes: Dict[str, Any] = field(default_factory=dict)        # printed on an earlier line

    # ---- what several readers select the same way
    def inside(self, ends: List[float]) -> List[int]:
        """Indices of the ticks or steps that ended inside the window."""
        return [i for i, t in enumerate(ends) if self.window_open < t <= self.window_close]

    def in_trace(self, ends: List[float]) -> List[int]:
        """Indices of the ticks or steps inside the traced part."""
        if self.traced is None:
            return []
        first, last = self.traced
        return [i for i in range(first, last + 1) if 0 <= i < len(ends)]

    def ttft_samples(self) -> List[float]:
        """First-token time minus due time of every answered request due in the window."""
        return [r.first_token - r.due for r in self.requests if r.in_window and r.first_token is not None]

    def itl_samples(self) -> List[float]:
        """Gaps between consecutive tokens of one request whose later token fell inside the window."""
        out = []
        for r in self.requests:
            t = r.token_times
            out.extend(b - a for a, b in zip(t, t[1:]) if self.window_open < b <= self.window_close)
        return out
