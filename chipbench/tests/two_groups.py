"""Test-only block family: the decoder family with its layers stored in
two stacked groups, the first layer alone and the rest after it."""
import jax

from chipbench.reference import decoder
from chipbench.reference import model as M
from chipbench.reference.decoder import (  # noqa: F401
    PROGRAM_FIELDS, flops_per_token, layer, program_rules)


def param_tree(c: dict) -> dict:
    tree = decoder.param_tree(c)
    (stacked,) = tree["groups"]
    n = c["num_hidden_layers"]
    tree["groups"] = tuple(
        jax.tree.map(lambda spec, r=rows: ((r,) + spec[0][1:], spec[1]),
                     stacked, is_leaf=M._is_spec)
        for rows in (1, n - 1))
    return tree


def split(one_group: dict) -> dict:
    """A one-group decoder's weights, laid out as this family stores them."""
    (stacked,) = one_group["groups"]
    return dict(one_group, groups=(jax.tree.map(lambda a: a[:1], stacked),
                                   jax.tree.map(lambda a: a[1:], stacked)))
