"""Device time of a traced window under a program's own tags.

``framework.name_scope(tag)`` puts a tag behind an op's scope in the HLO
op_name (``pt.fwd/mul/mla_proj``; a tag nested in another is joined to it by
a dot: ``pt.bwd/mul_grad/mtp.mla_proj``), which grad ops inherit.  This
reads them with ``part_scopes``' reduction (``op_scopes``' loader and
self-time rule), so its sums are parts of ``op_scopes``' sums.  A file of its
own: the benchmark's existing files are not edited.
"""

from __future__ import annotations

from typing import Optional, Sequence

from . import part_scopes


def train_ms_under(inputs: dict, tags: Sequence[str]) -> Optional[float]:
    """Milliseconds per traced training step of the device operations whose
    scope carries one of ``tags`` (full names as the program nests them, a
    longer name before the name it starts with); None without a trace that
    carries scopes, or where none of them is in it."""
    steps = inputs["counters"].get("steps_traced")
    by = part_scopes.seconds_by_part(inputs, tags)
    if not by or not steps:
        return None
    s = sum(sec for (_, _, tag), sec in by.items() if tag in tags)
    return s / steps * 1e3 if s > 0 else None
