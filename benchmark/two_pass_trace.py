"""What the readers of the two-pass cell's device metrics share: the seconds
a compiled program held the device, found by the program's name.

``reduce_trace.main_module`` gives the one program that held the device
longest; a two-pass job runs two sharded programs of nearly equal weight,
one a pass, so each reader names its own (``module`` in the metric's file).
Returns nothing where no program of that name ran (a parent from before
``parallel/batch_shard.py`` named a sweep's program after its kernel).
"""

from __future__ import annotations

from typing import Optional


def module_seconds(traced: dict, module: str) -> Optional[float]:
    """Device seconds (chip 0) of the executions whose name on the trace's
    ``XLA Modules`` line is ``<module>(<fingerprint>)``."""
    runs = [d for name, _, d in traced["trace"].modules
            if name.split("(", 1)[0] == module]
    return sum(runs) if runs else None
