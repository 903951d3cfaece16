"""Model step (``core/l2l.py``): model FLOP/s utilization of the traced
window, the model FLOPs of a token (``chipbench/flops.py``, recompute not
counted) times the window's tokens per second by the host's clock (all
tokens of its whole steps over its length, as ``train_tokens_per_s``),
over the chip's bf16 peak (``chipbench/peaks.py``).  Moves
``train_tokens_per_s``."""


def read(ctx):
    return 100.0 * ctx["tokens_per_s"] * ctx["flops_per_token"] \
        / ctx["peak"]["bf16_flops"]
