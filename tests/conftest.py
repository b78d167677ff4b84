import importlib.util
import os

import numpy as np
import pytest

# NOTE: no XLA_FLAGS here on purpose -- smoke tests and benches must see the
# host's real (single) device; only launch/dryrun.py forces 512.

# hypothesis is uninstallable on some hosts; fall back to a deterministic
# shim so the property-test modules still collect and run (see
# _hypothesis_compat.py).
try:
    import hypothesis  # noqa: F401
except ModuleNotFoundError:
    _spec = importlib.util.spec_from_file_location(
        "_hypothesis_compat",
        os.path.join(os.path.dirname(__file__), "_hypothesis_compat.py"))
    _mod = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_mod)
    _mod.install()


@pytest.fixture(autouse=True)
def _no_persistent_compile_cache(monkeypatch):
    """The entry points' main() turns on the persistent compilation cache
    (repro.api.compile_cache); tests that call a main() leave the
    process's JAX configuration alone."""
    from repro.api import compile_cache
    monkeypatch.setattr(compile_cache, "enable", lambda: None)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
