"""What one serving process may assume about the accelerator it runs on.

Two rules the entry points and the process-spawning paths share:

* **One process per chip.** A TPU chip belongs to the first process that
  touches jax, until that process exits. A child process that needs the
  chip afterwards fails or hangs. So the paths that spawn jax-building
  children (``--executor process``, ``--shard-execution process``) refuse
  with :class:`AcceleratorHeldError` before spawning whenever the parent's
  default backend is an accelerator, and ``"auto"`` never resolves to them
  there.
* **One compile cache.** :func:`enable_compilation_cache` is called by the
  entry points (``launch/serve.py``, ``launch/serve_backend.py``,
  ``chip_smoke.py``), never at import. Where ``JAX_COMPILATION_CACHE_DIR``
  is set, jax reads it and this module sets nothing; otherwise the cache
  lives at the fixed ``<checkout>/.jax_cache``, so a later run from the
  same checkout finds what an earlier one compiled.
"""

from __future__ import annotations

import os
import pathlib

import jax

# src/repro/runtime.py → the checkout root is two levels above the package
CHECKOUT_ROOT = pathlib.Path(__file__).resolve().parents[2]
DEFAULT_CACHE_DIR = CHECKOUT_ROOT / ".jax_cache"


class AcceleratorHeldError(RuntimeError):
    """A path would spawn jax-building children while this process holds
    the accelerator; the children could not reach the chip."""


def accelerator_attached() -> bool:
    """True when jax's default backend is an accelerator, not the host CPU."""
    return jax.default_backend() != "cpu"


def refuse_children_on_accelerator(what: str) -> None:
    """Raise :class:`AcceleratorHeldError` for ``what`` (a path that spawns
    processes which build jax state) when the default backend is an
    accelerator. Call it before spawning anything."""
    if accelerator_attached():
        raise AcceleratorHeldError(
            f"{what} spawns worker processes that build jax state, but this "
            f"process already holds the {jax.default_backend()} device and a "
            "chip belongs to one process at a time; use the in-process path "
            "(threads, or device execution for sharded search)"
        )


def enable_compilation_cache() -> str:
    """Turn on jax's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins and is left to jax.
    Otherwise the cache goes to the fixed :data:`DEFAULT_CACHE_DIR`: the
    directory is part of what a later run must find, so it is never built
    from a temporary name, a pid or the time.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
