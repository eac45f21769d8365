"""Device time per batch of the search program's fp32 rerank: the ops
under the ``ivf.rerank`` scope of ``_ivf_search``, over the search
module's events."""
from chipbench import spans


def read(run):
    return spans.scope_ms(run, "ivf.rerank")
