"""Exact sweep: device time of the full-graph PageRank
(``core.pagerank.pagerank``, module ``jit_pagerank``) per answer in the
traced window, in ms."""

MODULE = r"jit_pagerank$"


def read(record):
    from harness.trace import module_seconds

    if not record["trace"] or not record["answers"]:
        return None
    s = module_seconds(record["trace"], MODULE)
    return None if s is None else 1e3 * s / len(record["answers"])
