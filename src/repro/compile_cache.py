"""JAX's persistent compilation cache, kept at one fixed place.

A program that runs on the chip compiles the same kernels and steps on
every start; the persistent cache lets the next process read them back
from the directory the last one wrote:

* ``JAX_COMPILATION_CACHE_DIR``, when set, wins — JAX reads it itself
  and nothing here sets another path;
* otherwise the cache lives in ``.jax_cache/`` at the root of the
  checkout (ignored by git).

JAX's own thresholds stay as they are: a program that compiles in under
a second is not written.  A ``JAX_COMPILATION_CACHE_MAX_SIZE`` set
around the process caps the directory, least recently used first.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable` at start-up.  The library never calls it at import, and
tests do not: they compile on the CPU, and a chip-less compile for a
described TPU cannot be read back from a cache.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on for this process and
    return the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def usage(path: str) -> dict:
    """Entries and bytes of the cache at ``path`` (one ``*-cache`` file
    per compiled executable)."""
    files = list(pathlib.Path(path).glob("*-cache"))
    return {"dir": path, "entries": len(files),
            "bytes": sum(f.stat().st_size for f in files)}
