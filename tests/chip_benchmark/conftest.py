"""Tests of the chip benchmark (benchmark/chip). CPU only, toy sizes; no
topology is described and no chip is called, at import or later."""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(REPO, "benchmark", "chip")
for p in (CHIP, os.path.dirname(os.path.abspath(__file__))):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(scope="session")
def toy_root(tmp_path_factory):
    """A toy benchmark laid out in a temporary directory from data alone:
    configurations, traffic mixes, limits, per-layer metrics and its own
    BENCHMARK.json. Nothing under benchmark/chip is edited for it."""
    import toy
    return toy.lay_out(str(tmp_path_factory.mktemp("toy_benchmark")))


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    """The tests leave jax's persistent cache as they found it: a run on the
    chip places one (runner.enable_compile_cache), a test process must not,
    for the files that run after it in the same worker."""
    import runner
    monkeypatch.setattr(runner, "enable_compile_cache", lambda: None)
