"""recall@10 of the served ids against the exact ground truth, over
every answered request due in the window, in the GIST cell: its spread
from seed to seed is ten times SIFT's, so it has a bound of its own."""

from chipbench.readers import recall_at_10 as read  # noqa: F401
