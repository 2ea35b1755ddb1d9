"""Which way the arrows point between the program and its yardstick.

``benchmarks/`` (with ``BENCHMARK.json``) measures the program and
``chip_smoke.py`` drives it; both import it. The program imports
neither, so no benchmark file is ever part of what a cell measures.
"""

import ast
import json
import os

import pytest

from distributedtraining_tpu.utils import devprof

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROGRAM_DIRS = ("distributedtraining_tpu", "neurons", "scripts")
YARDSTICKS = {"benchmarks", "bench", "chip_smoke"}


def _imports(path):
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield node.lineno, a.name
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            yield node.lineno, node.module or ""


def test_program_imports_no_yardstick():
    seen = 0
    offenders = []
    for top in PROGRAM_DIRS:
        for root, _, files in os.walk(os.path.join(REPO, top)):
            for name in files:
                if not name.endswith(".py"):
                    continue
                seen += 1
                path = os.path.join(root, name)
                offenders += [
                    f"{os.path.relpath(path, REPO)}:{line} imports {mod}"
                    for line, mod in _imports(path)
                    if mod.split(".")[0] in YARDSTICKS]
    assert seen > 100          # the walk found the program
    assert not offenders, offenders


with open(os.path.join(REPO, "benchmarks", "peaks.json")) as _f:
    PEAKS = json.load(_f)


@pytest.mark.parametrize("kind", sorted(PEAKS))
def test_program_roofline_equals_the_benchmarks_peaks(kind):
    """While the program keeps a peak table of its own
    (``devprof.ROOFLINES``, ROADMAP D9 / D14), it says of every device
    the benchmark lists what ``benchmarks/peaks.json`` says. A test may
    read both; the program may not read the benchmark."""
    rl = devprof.roofline_for(kind)
    assert rl.known
    assert rl.peak_flops == PEAKS[kind]["bf16_flops_per_s"]
    assert rl.hbm_bytes_per_s == PEAKS[kind]["hbm_bytes_per_s"]
