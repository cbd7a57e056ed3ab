"""Device: share of the traced window in which no op ran on the chip
(1 - union of device-op intervals / window), in %."""


def read(record):
    t = record["trace"]
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
