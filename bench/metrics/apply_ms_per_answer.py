"""Engine apply: device time of the update scatter (``graph.add_edges``,
module ``jit__add_edges_impl``) per answer in the traced window, in ms."""

MODULE = r"jit__add_edges_impl$"


def read(record):
    from harness.trace import module_seconds

    if not record["trace"] or not record["answers"]:
        return None
    s = module_seconds(record["trace"], MODULE)
    return None if s is None else 1e3 * s / len(record["answers"])
