"""Device-idle seconds under the program's spans of one layer.

The program marks its layer seams as host spans named `<layer>.<step>`
(`repro.runtime.observe.span`). The trace reduction (devtrace.py) puts
each idle gap of the window, whole, under the innermost host span over
its middle, and keeps the ten largest names; this sums the gaps put
under one layer's spans.
"""
from __future__ import annotations

from typing import Optional


def idle_s_per_unit(run: dict, layer: str) -> Optional[float]:
    """Idle seconds under `<layer>.*` spans per unit of the window (per
    published batch); 0.0 where the traced device was never idle under
    them, nothing where the trace saw no device."""
    tr = run.get("trace")
    if not tr or tr["busy_s"] <= 0 or not run["units"]:
        return None
    prefix = layer + "."
    return sum(s for name, s in tr["breakdown"]["idle_gaps"]
               if name.startswith(prefix)) / run["units"]
