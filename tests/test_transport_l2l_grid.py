"""The ``l2l`` half of the slow (G, prefetch, pack, K) transport grid of
``test_transport.py``: every point must be bit-identical between the
xla and pallas slot movers.  A file of its own, so that test workers
given one file each run the two halves of the grid side by side."""
import pytest

from test_transport import FULL_GRID, check_train_point


@pytest.mark.slow
@pytest.mark.parametrize("name,group,prefetch,pack,stash",
                         [t for t in FULL_GRID if t[0] == "l2l"])
def test_train_bit_identical_full_grid(name, group, prefetch, pack, stash):
    check_train_point(name, group, prefetch, pack, stash)
