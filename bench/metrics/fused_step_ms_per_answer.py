"""Hot set, summary and summarized sweeps: device time of the fused query
step (``core.fused.fused_query_step``, module ``jit_fused_query_step``)
per answer in the traced window, in ms."""

MODULE = r"jit_fused_query_step$"


def read(record):
    from harness.trace import module_seconds

    if not record["trace"] or not record["answers"]:
        return None
    s = module_seconds(record["trace"], MODULE)
    return None if s is None else 1e3 * s / len(record["answers"])
