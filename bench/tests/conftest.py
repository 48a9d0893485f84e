import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(scope="session")
def bench_state(tmp_path_factory):
    return str(tmp_path_factory.mktemp("bench-state"))


@pytest.fixture
def tiny(monkeypatch, bench_state):
    """The tiny cells' environment: run-time state in a temporary
    directory shared by the session (so plans are solved once),
    the tiny model config resolvable by name, and the process-wide plan
    store default put back afterwards."""
    import repro.configs
    from repro.store import planstore

    from bench.harness import core
    from bench.tests import tiny as t
    real = repro.configs.get_config
    monkeypatch.setattr(core, "STATE", bench_state)
    monkeypatch.setattr(
        repro.configs, "get_config",
        lambda name: t.tiny_model_config()
        if name == t.MODEL["program_config"] else real(name))
    saved = planstore._DIR_OVERRIDE
    yield t
    planstore.set_default_dir(saved)
