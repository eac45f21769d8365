"""recall@10 of the served ids against the exact ground truth, over
every answered request due in the window, in the SIFT cells."""

from chipbench.readers import recall_at_10 as read  # noqa: F401
