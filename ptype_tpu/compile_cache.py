"""Persistent XLA compile cache — where it lives, decided in one place.

Every entry point that compiles (the ``__main__`` commands, the optimus
trainer, the replica worker, ``bench.py --worker``, ``chip_smoke.py``)
calls :func:`configure` before its first trace, so a restarted trainer,
a spawned replica and a second smoke run reuse the 125M step instead of
compiling it from cold.

The directory is part of the cache key's lookup, so it must not move
between runs: ``$JAX_COMPILATION_CACHE_DIR`` when the operator set it
(JAX reads that variable itself — nothing is set in code then),
otherwise ``<checkout>/.jax_cache``, a fixed path derived from the
package location (git-ignored).
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def configure() -> str:
    """Point JAX at the persistent compile cache; returns the directory
    in use. Must run before the process's first compilation — JAX
    opens the cache once."""
    import jax

    # An executable carries its HLO metadata (op_name: the model's
    # named scopes; source lines), and JAX leaves metadata out of the
    # cache key by default: a hit would then bring back the names of
    # whichever commit compiled the program first, and a device trace
    # would show those (chip run, PR 25: the decode step came back from
    # PR 24's cache without a single scope). Keyed on metadata too, a
    # program is compiled again when its names or lines move.
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      True)
    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
