"""The benchmark's own tests: CPU only, small shapes. Run from the root of
the checkout with ``JAX_PLATFORMS=cpu python -m pytest perfbench/tests``."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    """CPU runs keep nothing in the checkout's compile cache: entries
    written here would be stale on the chip's machine."""
    from perfbench import harness

    monkeypatch.setattr(harness, "setup_jax_cache", lambda: None)
