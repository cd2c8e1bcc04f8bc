import os
import pathlib

from p1_tpu.hashx.backend import (
    HashBackend,
    SearchResult,
    available_backends,
    get_backend,
    register,
)

# Import for registration side effects.
from p1_tpu.hashx import cpu as _cpu  # noqa: F401
from p1_tpu.hashx import numpy_backend as _numpy  # noqa: F401

# Backends with heavy imports (JAX) or build steps (native .so) load lazily.
from p1_tpu.hashx.backend import register_lazy as _register_lazy


def _load_jax():
    configure_compile_cache()
    from p1_tpu.hashx import jax_backend

    return jax_backend.JaxBackend


def _load_sharded():
    configure_compile_cache()
    from p1_tpu.hashx import sharded

    return sharded.ShardedBackend


def _load_pallas():
    configure_compile_cache()
    from p1_tpu.hashx import pallas_backend

    return pallas_backend.PallasTPUBackend


def _load_native():
    from p1_tpu.hashx import native_backend

    return native_backend.NativeBackend


_register_lazy("jax", _load_jax)
_register_lazy("sharded", _load_sharded)
_register_lazy("tpu", _load_pallas)
_register_lazy("native", _load_native)


#: The persistent compile cache's fixed home when ``JAX_COMPILATION_CACHE_DIR``
#: is unset: inside the checkout (listed in .gitignore), so every process
#: run from one checkout shares it.  The path is part of the cache key,
#: so it is never built from a temporary name, a pid or the time.
COMPILE_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> None:
    """Point JAX's persistent compilation cache at its one directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it itself and no
    directory is set here; otherwise the cache is ``COMPILE_CACHE_DIR``.
    Called wherever the program first configures JAX (the device hash
    backends, device replay, the device MSM, ``__graft_entry__`` and
    ``chip_smoke.py``), before the process's first compile: JAX decides
    once per process whether a cache is in use.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))


__all__ = [
    "HashBackend",
    "SearchResult",
    "available_backends",
    "COMPILE_CACHE_DIR",
    "configure_compile_cache",
    "get_backend",
    "register",
]
