"""Engine jit cache: programs compiled or loaded from the persistent
cache inside the measured window (JAX's backend-compile events)."""


def read(record):
    return float(record["compiles_in_window"])
