"""Nearest-rank p90 of the gap between consecutive generated tokens of one
request (differences of ``token_ms`` on the ``serving.decode`` spans), pooled
over the requests completed in the window."""

from ..reading import named
from ..stats import percentile


def read(inputs):
    gaps = []
    for s in named(inputs, "serving.decode"):
        t = s[3].get("token_ms") or []
        gaps += [b - a for a, b in zip(t, t[1:])]
    return percentile(gaps, 90) if gaps else None
