"""1 - busy / window of the profiler's trace."""

from ..reading import idle_share as read  # noqa: F401
