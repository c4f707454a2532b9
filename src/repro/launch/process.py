"""Process set-up shared by the entry points: the compile cache, and
keeping helper processes off the accelerator.

Library imports never call these; the launchers, the benchmark harness and
``chip_smoke.py`` do.
"""

from __future__ import annotations

import contextlib
import os
from pathlib import Path
from typing import Optional

# <checkout>/.jax_cache: a fixed path, so the cache is found again by the
# next process that runs from the same checkout (git-ignored)
_CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads
    it itself) and no other directory is set.  Otherwise an accelerator's
    cache lives at ``<checkout>/.jax_cache``, and the CPU backend keeps
    none (None): its compiles are quick, and an XLA:CPU result is tied to
    the instruction set of the host that compiled it."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    if jax.default_backend() == "cpu":
        return None
    path = str(_CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@contextlib.contextmanager
def cpu_only_children():
    """Processes started inside this block see ``JAX_PLATFORMS=cpu``.

    A chip belongs to one process at a time: a helper process (front-end
    worker, IPC probe echo child) that initialised a JAX backend would fight
    the parent for it.  ``multiprocessing`` spawn copies the parent's
    environment at ``start()``, so the variable is set around the start
    and restored after; the parent's own backend is long initialised and
    does not read it again."""
    old = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("JAX_PLATFORMS", None)
        else:
            os.environ["JAX_PLATFORMS"] = old
