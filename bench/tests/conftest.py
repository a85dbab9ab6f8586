"""bench/tests run on the CPU, by hand:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

They are not part of the repository's tier-1 tests (``tests/``).
"""

import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest


@pytest.fixture
def small_cell(monkeypatch, tmp_path):
    """A committed cell cut to a size a test run can hold, on the CPU
    with the TPU's resident-tier path and a compile cache of its own."""
    import run

    monkeypatch.setenv("YDB_TPU_RESIDENT", "1")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path / "trace")

    def load(workload: str, scale_factor: float = 0.01) -> dict:
        cell = run.load_cell(workload)
        cell["config"]["scale_factor"] = scale_factor
        # at a test's size on the CPU the program picks other executors
        # than the chip's cells expect
        cell["traffic"].pop("executors")
        return cell

    return load
