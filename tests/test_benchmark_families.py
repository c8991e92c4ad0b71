"""Tier-1 holds the benchmark's families whole: the cases of
``benchmark/tests/test_families.py`` (every configuration of BENCHMARK.json
names a family with its four files and their functions, every workload's
cell resolves, an unknown or half family is refused in a sentence) and the
rehearsal of the xing4 family (``benchmark/tests/test_rehearsal_xing4.py``: a
whole serving run at a toy width to ``correct`` on the CPU) and of the afmoe
family (``test_rehearsal_afmoe.py``, with its planted faults) and of the lfm2
family (``test_rehearsal_lfm2.py``, likewise) and of the jamba family
(``test_rehearsal_jamba.py``, likewise) run here too, imported and not
copied, so that a cell or a family that no longer loads
fails the tests the driver runs. So do the readers of the xing4 cell's own
per-layer metrics on their hand-made context
(``benchmark/tests/test_xing4_readers.py``; ``test_afmoe_readers.py`` and
``test_lfm2_readers.py`` and ``test_jamba_readers.py`` for those cells'),
and those of the program's spans
and counters (``benchmark/tests/test_program_span_readers.py``: the engine's
phase clock; ``benchmark/tests/test_greedy_tail_share.py``: ``sampled_steps``;
``benchmark/tests/test_uploads_per_dispatch.py``: ``paged_uploads``),
which break when the program renames what they read."""
import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_TESTS = os.path.join(ROOT, "benchmark", "tests")
# what benchmark/tests/conftest.py puts on the path for its own run
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _cases(stem):
    spec = importlib.util.spec_from_file_location(
        "benchmark_tests_" + stem, os.path.join(BENCH_TESTS, stem + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return {k: v for k, v in vars(mod).items() if k.startswith("test_")}


@pytest.fixture(autouse=True)
def _keep_the_workers_arrays(monkeypatch):
    """A run of the harness ends with ``sut.free_device_memory()``, which
    deletes every live array of the process: right for a benchmark process
    at 11 GB, fatal for the other test modules of a tier-1 worker (the
    framework's global random state is such an array). Nothing needs
    freeing at the toy width."""
    from benchmark.harness import sut
    monkeypatch.setattr(sut, "free_device_memory", lambda: None)


globals().update(_cases("test_families"))
globals().update(_cases("test_rehearsal_xing4"))
globals().update(_cases("test_xing4_readers"))
globals().update({k + "_afmoe" if k in globals() else k: v for k, v in
                  _cases("test_rehearsal_afmoe").items()})
globals().update(_cases("test_afmoe_readers"))
globals().update({k + "_lfm2" if k in globals() else k: v for k, v in
                  _cases("test_rehearsal_lfm2").items()})
globals().update({k + "_lfm2" if k in globals() else k: v for k, v in
                  _cases("test_lfm2_readers").items()})
globals().update({k + "_jamba" if k in globals() else k: v for k, v in
                  _cases("test_rehearsal_jamba").items()})
globals().update({k + "_jamba" if k in globals() else k: v for k, v in
                  _cases("test_jamba_readers").items()})
globals().update(_cases("test_program_span_readers"))
globals().update(_cases("test_greedy_tail_share"))
globals().update(_cases("test_uploads_per_dispatch"))
