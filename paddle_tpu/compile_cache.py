"""Where JAX's persistent compilation cache lives.

Entry points (chip_smoke.py, benchmarks/common.parse_args,
__graft_entry__) call ``configure()`` before their first compile;
``import paddle_tpu`` does not — a library import must not decide where
a process writes.

The directory is part of the cache key, so it never moves: where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
directory is set in code; otherwise it is ``<checkout>/.jax_cache``
(listed in .gitignore) — never a temp name, a pid or a timestamp.
"""

import os

import jax

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def configure():
    """Place the cache; returns the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


def entries(directory):
    """Number of cached executables in ``directory`` (0 if absent)."""
    try:
        return sum(1 for n in os.listdir(directory)
                   if not n.endswith("-atime"))
    except OSError:
        return 0
