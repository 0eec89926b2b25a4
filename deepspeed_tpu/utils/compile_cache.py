"""JAX's persistent compile cache, placeable from outside.

Called by the entry points that compile for the chip (``chip_smoke.py``,
``bench.py``, ``bin/ds_tpu_serve``, ``examples/train.py``). Where
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing here
sets another directory; where it is not, the cache lives at
``<checkout>/.jax_cache`` — a fixed path, because the path is part of what
makes a later run find the entries again.
"""

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    import jax
    # JAX leaves metadata out of the cache's key by default, so a program
    # that differs from a cached one in its names alone (a ``named_scope``
    # added, a line moved) is handed the OTHER program's executable, whose
    # HLO text and whose profile carry the other's names: the scope tables
    # (``Tracer.scope_tables``) and a device trace would then describe code
    # that is not running. With the metadata in the key an executable's
    # names are those of the program that asked for it.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def cache_entries(path: str) -> int:
    """Number of compiled programs the cache directory holds."""
    if not os.path.isdir(path):
        return 0
    return sum(1 for name in os.listdir(path) if not name.endswith("-atime"))
