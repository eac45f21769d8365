"""Device time per batch of the search program's cell scan: the ops
under the ``ivf.scan`` scope of ``_ivf_search`` (the cell gather, the
int8 dequantise and the scan dots), over the search module's events."""
from chipbench import spans


def read(run):
    return spans.scope_ms(run, "ivf.scan")
