"""Model step (``core/l2l.py``): share of the traced window in which the
chip runs the layers' backward (per-microbatch vjp, gradient sums, the
finite check, clip and norm), the instructions under the program's
``l2l.backward`` scope, each instant counted once
(``chipbench/phases.py``).  Nothing to read from a program without the
scopes.  Moves ``train_tokens_per_s``."""
from chipbench import phases


def read(ctx):
    return phases.share(ctx, "l2l.backward")
