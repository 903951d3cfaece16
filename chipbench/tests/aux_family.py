"""Test-only block family: the decoder's block with an auxiliary loss
per sequence, shaped like a sequence-wise expert balance loss.

Each token spreads over ``SLOTS`` slots by a softmax of the first
``SLOTS`` features of the block's output; a sequence's loss is
``WEIGHT * SLOTS * sum_e f_e^2`` of its tokens' mean spread ``f``
(``WEIGHT`` when the spread is even), and the layer's aux is the mean
over the block's sequences.
"""
import jax
import jax.numpy as jnp

from chipbench.reference import decoder
from chipbench.reference.decoder import (  # noqa: F401
    PROGRAM_FIELDS, flops_per_token, param_tree, program_rules)

SLOTS, WEIGHT, SHARPNESS = 8, 0.01, 50.0


def layer(c, ar, w, x, q_block, group):
    y, _ = decoder.layer(c, ar, w, x, q_block, group)
    spread = jax.nn.softmax(SHARPNESS * y[..., :SLOTS], axis=-1)
    f = spread.mean(axis=1)                                  # (b, slots)
    return y, (WEIGHT * SLOTS * jnp.sum(f * f, axis=-1)).mean()
