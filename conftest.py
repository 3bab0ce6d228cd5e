"""The chip benchmark's serving driver, after its window, deletes every
live device array (``free_device``) so that the reference runs on an empty
chip.  Tests that run a cell share their process with other test modules,
some of which keep device arrays in caches (the policy substrates, the
solvers): every test's deletions are kept to the arrays it made itself."""
import jax
import pytest


@pytest.fixture(autouse=True)
def _free_only_own_arrays(monkeypatch):
    before = {id(a) for a in jax.live_arrays()}
    live = jax.live_arrays
    monkeypatch.setattr(jax, "live_arrays",
                        lambda: [a for a in live() if id(a) not in before])
