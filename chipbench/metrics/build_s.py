"""Host seconds around the index build (k-means and layout), ended by a
device sync."""


def read(run):
    return run.build_s
