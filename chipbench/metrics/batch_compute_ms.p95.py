"""Median of the tier's exact per-response compute_ms (the host span of
execute_search_batch, ended by block_until_ready), in the open cell."""

from chipbench.readers import median_compute_ms as read  # noqa: F401
