"""95th percentile, by nearest rank, of the tier's exact per-response
queue_wait_ms (submit to batch formed)."""

from chipbench.readers import queue_wait_p95_ms as read  # noqa: F401
