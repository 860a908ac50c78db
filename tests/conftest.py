"""Test env: force XLA-CPU with 8 virtual devices BEFORE jax import.

This is the fake-device strategy from SURVEY.md §4: the reference tests
distributed code with Gloo/custom-device fakes on localhost; here an
8-device CPU mesh exercises the same sharding/collective paths the TPU
uses.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# The suite keeps the persistent compile cache off: the compile-only
# TPU entries tests/test_tpu_compile.py would write cannot be read back
# without a chip (every later run would warn and recompile), and tests
# must not depend on what an earlier run left on disk.
jax.config.update("jax_enable_compilation_cache", False)

import gc  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _drop_jax_caches_between_modules():
    """The full suite compiles hundreds of XLA CPU executables; letting
    them accumulate has intermittently aborted (SIGABRT) late heavy
    tests (observed: llama backward in test_models).  Dropping compiled
    caches at module boundaries keeps the process footprint flat."""
    yield
    try:
        jax.clear_caches()
    except Exception:
        pass
    gc.collect()


@pytest.fixture(autouse=True)
def _tiny_afmoe(request):
    """``benchmarks/tests/test_benchmark.py`` (collected here through
    ``tests/test_benchmark.py``) runs every cell of the manifest from
    its own ``TINY`` table, keyed by family, and a PR that adds a family
    may not edit that file, nor ``benchmarks/conftest.py``: hand the
    table its ``afmoe`` and ``qwen3_next`` entries from here
    (``benchmarks/tests/test_trinity_mini_cell.py`` and
    ``test_qwen3_next_cell.py`` hold them).  The next ``benchmark`` PR
    moves the entries into the table and this goes (PERF.md section
    7)."""
    table = getattr(request.module, "TINY", None)
    if isinstance(table, dict) and "afmoe" not in table:
        from benchmarks.tests.test_trinity_mini_cell import TINY_AFMOE
        table["afmoe"] = TINY_AFMOE
    if isinstance(table, dict) and "qwen3_next" not in table:
        from benchmarks.tests.test_qwen3_next_cell import TINY_QWEN3_NEXT
        table["qwen3_next"] = TINY_QWEN3_NEXT
