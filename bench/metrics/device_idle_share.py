"""Share of the traced plans' window in which no operation ran on the
device: 100 (1 - union of device op intervals / window)."""


def read(record):
    tr = record["trace"]
    if tr["window_s"] <= 0 or not tr["devices"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
