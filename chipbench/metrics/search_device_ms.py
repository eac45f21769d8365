"""Device time of the jitted search module per batch, from the trace."""

from chipbench.readers import search_device_ms as read  # noqa: F401
