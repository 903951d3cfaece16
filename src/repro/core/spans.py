"""The phases of the L2L train step, as ``jax.named_scope`` names.

A scope is metadata: JAX writes the path of the scopes an operation was
traced under into its HLO instruction's ``op_name`` (for example
``jit(step)/while/body/closed_call/l2l.backward/l2l.optimizer/mul``) and
runs nothing for it.  A profile of the chip keys each device instant by
its instruction, so ``phase`` of that instruction's ``op_name`` says which
part of the step the instant belongs to.  Scope names hold no ``/``.
"""
from __future__ import annotations

from typing import Optional

FORWARD = "l2l.forward"        # a layer's forward over the microbatches
BACKWARD = "l2l.backward"      # per-microbatch vjp, gradient sums, checks
RECOMPUTE = "l2l.recompute"    # re-forward of a stash segment (K > 1)
OPTIMIZER = "l2l.optimizer"    # the optimizer's arithmetic
HEAD = "l2l.head"              # embedding, head and loss, transitions
FETCH = "relay.fetch"          # every host -> HBM move
WRITEBACK = "relay.writeback"  # every HBM -> host move
# a relay scan's own loop: the scan slices its xs (the stash the
# backward fetches) and stacks its ys (what the stops ship back) itself,
# outside the body, so those instructions carry the scope of the scan's
# call site; ``phase`` reads them as the moves they are
SCAN = "relay.scan"

SCOPES = (FORWARD, BACKWARD, RECOMPUTE, OPTIMIZER, HEAD, FETCH, WRITEBACK,
          SCAN)
_SCAN_MOVES = {"dynamic_slice": FETCH, "dynamic_update_slice": WRITEBACK}


def phase(op_name: str) -> Optional[str]:
    """The innermost scope of ``SCOPES`` in an ``op_name`` path, or None
    for an instruction traced under none of them.  An instruction of a
    relay scan's own loop is ``FETCH`` where it slices the scan's xs,
    ``WRITEBACK`` where it stacks the scan's ys, and None where it is
    the loop's bookkeeping."""
    parts = op_name.split("/")
    for part in reversed(parts):
        if part == SCAN:
            return _SCAN_MOVES.get(parts[-1])
        if part in SCOPES:
            return part
    return None
