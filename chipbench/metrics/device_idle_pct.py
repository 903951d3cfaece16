"""Device: share of the traced window in which no operation runs on the
chip, 1 - (union of the device's operation intervals) / window, from the
device plane of the trace (``chipbench/trace.py``).  Moves
``train_tokens_per_s``."""


def read(ctx):
    t = ctx["trace"]
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
