"""What a driver's measured window returns."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Window:
    end_to_end: dict        # end-to-end metric name -> value
    attempted: int          # units of work started in the window
    failed: int             # of those, how many raised or were refused
    counters: dict          # what the per-layer readers read
