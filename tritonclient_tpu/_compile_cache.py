"""Where JAX's persistent compilation cache lives.

Every entry script that compiles (``chip_smoke.py``, ``bench.py``,
``scripts/genai_bench.py``, ``scripts/aio_bench.py``,
``__graft_entry__.py``) calls ``configure()`` before its first jit, so a
second process — or a second call on a machine that keeps the directory —
loads BERT-base, its batch buckets and the engine's prefill/decode family
instead of compiling them again.

The directory is part of the cache key's lookup, so it never moves: where
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing here
sets another; otherwise it is ``<checkout>/.jax_cache`` (git-ignored).
"""

import os
from typing import Optional

ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def configure() -> str:
    """Place the cache; returns the directory in use."""
    placed = os.environ.get(ENV)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", _CHECKOUT_DIR)
    return _CHECKOUT_DIR


def entry_count(directory: Optional[str]) -> int:
    """Executables the cache directory holds (0 when there is none)."""
    if not directory:
        return 0
    try:
        return sum(name.endswith("-cache") for name in os.listdir(directory))
    except FileNotFoundError:
        return 0
