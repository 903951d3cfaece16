"""EPS: bytes of the train state's arrays that live in ``pinned_host``
after the window (the stacked layer rows and their Adam slots).  Moves
``peak_host_gb``."""
import jax


def read(ctx):
    return sum(a.nbytes for a in jax.tree.leaves(ctx["state"])
               if getattr(a.sharding, "memory_kind", None)
               == "pinned_host") / 1e9
