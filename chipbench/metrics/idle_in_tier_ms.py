"""Device idle time per batch that falls inside a ``serve.batch`` span
and outside its ``serve.wait``: the idle that overlapping the tier's
host work with the device would take away."""
from chipbench.spans import idle_in_tier_ms as read  # noqa: F401
