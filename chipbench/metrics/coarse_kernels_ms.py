"""Device time of the coarse route's two Pallas kernels (distance and
top-k) per batch, from the trace."""

from chipbench.readers import coarse_kernels_ms as read  # noqa: F401
