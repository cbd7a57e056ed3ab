"""Kernels: share of the chip's HBM roofline that the window's exact
sweeps reach, in %: the bytes they need (``harness.exact_work``, from
each answer's iterations, live edges and active vertices) over the
device seconds of module ``jit_pagerank`` times the peak bandwidth."""

MODULE = r"jit_pagerank$"


def read(record):
    from harness.exact_work import window_bytes
    from harness.trace import module_seconds

    if not record["trace"] or not record["peaks"]:
        return None
    s = module_seconds(record["trace"], MODULE)
    work = window_bytes(record["answers"])
    if not s or not work:
        return None
    return 100.0 * work / (s * record["peaks"].hbm_bytes_per_s)
