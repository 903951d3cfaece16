"""Engine facade (``engine/engine.py``): programs lowered or compiled
inside the measured window, from JAX's compile events.  Anything above 0
is compile time billed to training.  Moves ``train_tokens_per_s``."""


def read(ctx):
    return ctx["window_compiles"]
