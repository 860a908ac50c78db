"""``benchmarks/tests/test_longcat_flash_cell.py`` in tier-1: its tests
and the two autouse fixtures of the benchmark's conftests, under this
directory's environment (CPU, 8 virtual devices, compile cache off), so
that a change to the program that breaks ``benchmarks/run.py``'s use
of it fails here and not in a chip check."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from benchmarks.conftest import _tiny_minicpm_sala  # noqa: E402,F401
from benchmarks.tests.conftest import (  # noqa: E402,F401
    _recorded_cut_is_the_newest_trace)
from benchmarks.tests.test_longcat_flash_cell import *  # noqa: E402,F401,F403
