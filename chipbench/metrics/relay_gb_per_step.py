"""Relay (``core/relay.py``): gigabytes the relay moves between host and
HBM in one train step, fetch plus write-back, by the program's own count
at its move sites (``Engine.counters()``): the run's bytes over its
steps.  Every step of a cell runs the one compiled step, so this is the
window's bytes over its steps.  The benchmark's ``ctx`` does not hold the
engine; the engine is the one live ``Engine`` of this process.  Nothing
to read from a program without the counter.  Moves
``train_tokens_per_s``."""
import gc


def read(ctx):
    from repro.engine.engine import Engine
    engines = [o for o in gc.get_objects() if isinstance(o, Engine)]
    if len(engines) != 1 or not hasattr(engines[0], "counters"):
        return None
    c = engines[0].counters()
    if not c["train_steps"]:
        return None
    return (c["relay_fetch_bytes"] + c["relay_writeback_bytes"]) \
        / c["train_steps"] / 1e9
