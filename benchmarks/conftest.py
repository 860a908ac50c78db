"""``benchmarks/tests/test_benchmark.py`` runs every cell of the
manifest at a tiny size from its own ``TINY`` table, keyed by family,
and a PR that adds a family may not edit that file.  This hands the
table the ``minicpm_sala`` entry (``tests/test_minicpm_sala_cell.py``
holds it) before a test of that module runs; the next ``benchmark`` PR
moves the entry into the table and deletes this file (PERF.md section
7)."""
import pytest


@pytest.fixture(autouse=True)
def _tiny_minicpm_sala(request):
    table = getattr(request.module, "TINY", None)
    if isinstance(table, dict) and "minicpm_sala" not in table:
        from benchmarks.tests.test_minicpm_sala_cell import TINY_SALA
        table["minicpm_sala"] = TINY_SALA
