"""Optimizer (``optim/``, run by ``core/l2l.py``): share of the traced
window in which the chip runs the optimizer's update, eager per layer and
of the embedding and head, the instructions under the program's
``l2l.optimizer`` scope, each instant counted once
(``chipbench/phases.py``).  Nothing to read from a program without the
scopes.  Moves ``train_tokens_per_s``."""
from chipbench import phases


def read(ctx):
    return phases.share(ctx, "l2l.optimizer")
