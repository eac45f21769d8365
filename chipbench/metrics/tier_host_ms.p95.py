"""Host time per batch of the serving tier, in the open cell: the median
over the window's ``serve.batch`` spans of the span less its
``serve.wait`` child (forming, dispatch, copies back, delivery)."""
from chipbench.spans import tier_host_ms as read  # noqa: F401
