"""The least time a batch of the search needs (the larger of its
operations over the peak and its bytes over HBM bandwidth, counted from
the search's semantics) over the device time it took, in %."""

from chipbench.readers import search_roofline as read  # noqa: F401
