"""Nearest-rank p90, over the requests completed in the window (the start
batch excluded), of (completion - submission) / generated tokens on the
dispatcher's clock: the Orca / vLLM normalised latency.  A property of which
requests the window holds as much as of the system (PERF.md), so it is read
per layer and carries no bound."""

from ..stats import percentile


def read(inputs):
    lat = inputs["facts"].get("norm_latency_ms")
    return percentile(lat, 90) if lat else None
