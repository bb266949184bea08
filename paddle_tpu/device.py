"""Device identity and compile-cache placement — the two facts about the
machine every layer must agree on.

* "On a TPU" has ONE definition: the platform string is ``"tpu"``.  No
  ``try/except`` around the query: a backend that cannot initialise is an
  error to raise, never a reason to run somewhere else quietly.
* The persistent XLA compile cache has ONE placement rule
  (:func:`place_compile_cache`), applied when the package is imported so a
  trainer, a server and the benchmark all start with the same cache.
"""

from __future__ import annotations

import os

import jax

__all__ = ["is_tpu", "on_tpu", "tpu_device", "place_compile_cache",
           "DEFAULT_COMPILE_CACHE_DIR"]

#: ``<checkout>/.cache/xla_compile``, from the package's own location: the
#: path is part of the cache key, so it must not move between runs
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".cache", "xla_compile")


def is_tpu(device) -> bool:
    """Whether ``device`` (a ``jax.Device``) is a TPU chip."""
    return device.platform == "tpu"


def on_tpu() -> bool:
    """Whether JAX's default backend is the TPU.  Initialises the backend
    and raises if that fails."""
    return jax.default_backend() == "tpu"


def tpu_device(ordinal: int):
    """The TPU chip with local ordinal ``ordinal`` (``TPUPlace(ordinal)``),
    or a ``RuntimeError`` naming what JAX found instead."""
    tpus = [d for d in jax.devices() if is_tpu(d)]
    if not 0 <= ordinal < len(tpus):
        found = ", ".join(sorted({f"{d.platform}:{d.device_kind}"
                                  for d in jax.devices()}))
        raise RuntimeError(
            f"TPUPlace({ordinal}) needs a TPU with ordinal {ordinal}, but "
            f"JAX found {len(tpus)} TPU device(s) (devices: {found}). "
            "Use Executor() to run on the default backend (CPU tests), "
            "and Executor(TPUPlace(i)) only on a chip.")
    return tpus[ordinal]


def place_compile_cache(flag_dir: str = "") -> str:
    """Place JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing
    here (nor ``FLAGS_xla_compile_cache_dir``) overrides or clears it.
    Otherwise the cache is ``flag_dir`` when given, else
    :data:`DEFAULT_COMPILE_CACHE_DIR`.  In every case HLO metadata (source
    lines, named scopes) is made part of the cache key.  Touches no
    backend."""
    # Wherever the cache is: the key covers the HLO metadata too.  JAX leaves
    # it out by default, and an executable cached by a commit that named its
    # operations otherwise (or not at all) is then loaded for this one: its
    # profile carries the old names, and ``pt.<role>/<op>`` scopes never reach
    # the device trace (measured on a v5e, PR 24: a cache the parent commit
    # had filled gave 5 hits and no scoped operation).
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    cache_dir = flag_dir or DEFAULT_COMPILE_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
