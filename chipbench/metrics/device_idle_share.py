"""Share of the traced window in which no op ran on the device, in %."""

from chipbench.readers import device_idle_share as read  # noqa: F401
