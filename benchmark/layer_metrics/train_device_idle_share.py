"""1 - busy / window of the profiler's trace, mean over chips."""

from ..reading import idle_share as read  # noqa: F401
