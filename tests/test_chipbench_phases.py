"""The benchmark's phase reduction, in the tier-1 run: the tests of
``chipbench/tests/test_phases.py`` (``pytest.ini`` collects ``tests/``
only)."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench.tests.test_phases import (  # noqa: E402,F401
    test_hlo_op_names_reads_the_profiles_own_hlo,
    test_phases_read_relay_wait_and_idle_as_the_trace_reduction,
    test_phases_wait_and_idle_add_up_to_the_window_to_the_nanosecond)
