"""Process set-up shared by the entry points: JAX's persistent compilation
cache, and the check that a chip-path process really holds a TPU.

Entry points (``launch/serve.py``, ``serving/worker.py``,
``benchmarks/run.py``, ``chip_smoke.py``) call these from ``main``; nothing
here runs on import.
"""
from __future__ import annotations

import os
from pathlib import Path

# <repo>/.jax_cache: a fixed path inside the checkout (the path is part of
# the cache key, so a moving directory would never hit); .gitignore lists it
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


class PlatformError(RuntimeError):
    """A process meant for one platform came up on another — raised rather
    than letting a chip-path run finish on the CPU."""


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; → its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads the variable itself
    and nothing is set here; otherwise the cache lives at ``REPO_CACHE``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)


def require_platform(platform: str = "tpu"):
    """→ ``jax.devices()[0]`` if it is on ``platform``, else PlatformError.
    JAX falls back to the CPU when the chip cannot be had; this is the check
    that turns that fallback into a failure."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != platform:
        raise PlatformError(f"expected a {platform!r} device, JAX gave "
                            f"{dev.platform!r} ({dev.device_kind})")
    return dev
