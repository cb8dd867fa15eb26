import pytest

from bench import harness


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    """Runs in the tests leave JAX's persistent compilation cache as the
    rest of the suite has it: the benchmark's cache settings are for the
    chip."""
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
