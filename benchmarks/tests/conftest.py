"""The benchmark's own tests: `python -m pytest benchmarks/tests -q`, on the
CPU. They are not part of the repository's tier-1 run."""

import importlib
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)


def _load_run_cell(root):
    """Import the copy's run_cell (and its drivers, readers, traffic) in
    place of any earlier copy's."""
    for name in [m for m in sys.modules if m.split(".")[0] in (
            "run_cell", "drivers", "readers", "traffic", "reference")]:
        del sys.modules[name]
    bench = os.path.join(root, "benchmarks")
    sys.path[:] = [p for p in sys.path if not p.endswith("benchmarks")]
    sys.path.insert(0, bench)
    return importlib.import_module("run_cell")


@pytest.fixture
def tiny_checkout(tmp_path, monkeypatch):
    import tiny
    root = tiny.copy_with_tiny(tmp_path)
    saved = list(sys.path)
    run_cell = _load_run_cell(root)
    from drivers import common
    monkeypatch.setattr(common, "require_device", lambda chips: {
        "platform": "cpu-rehearsal", "kind": "TPU v5 lite", "count": chips})
    monkeypatch.setattr(common, "WORK_DIR", str(tmp_path / "work"))
    yield run_cell
    sys.path[:] = saved
    _load_run_cell(ROOT)
