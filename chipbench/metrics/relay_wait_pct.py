"""Relay (``core/relay.py``): share of the traced window in which the
chip waits on a host<->HBM transfer and runs nothing else: time under an
async ``*-start`` or ``*-done`` instruction on host memory (``S(5)``, see
``chipbench/trace.py``) not covered by any other instruction.  A wait the
trace does not mark as one, such as an instruction stalled behind the
relay's transfers, is not counted, so this is a lower bound.  Nothing to
read where the trace holds no transfer.  Moves ``train_tokens_per_s``."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["has_transfers"]:
        return None
    return 100.0 * t["relay_wait_s"] / t["window_s"]
