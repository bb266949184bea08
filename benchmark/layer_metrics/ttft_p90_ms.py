"""Nearest-rank p90 of the time to the first token (``ttft_ms`` of the
``serving.decode`` spans: submission to the first generated token on the
scheduler's clock) over the requests completed in the window."""

from ..reading import named
from ..stats import percentile


def read(inputs):
    ttft = [s[3]["ttft_ms"] for s in named(inputs, "serving.decode")
            if "ttft_ms" in s[3]]
    return percentile(ttft, 90) if ttft else None
