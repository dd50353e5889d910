# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
"""Shared kernel-layer runtime helpers."""
from __future__ import annotations

import os
import pathlib
from typing import Optional

import jax

# <repo>/.jax_cache: a fixed path, since the path is part of what the
# persistent cache is keyed on.
_REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def default_interpret() -> bool:
    """Whether Pallas kernels should run in interpret mode by default.

    Interpret mode emulates the TPU grid on the host — required in CPU
    containers, pure overhead on real hardware.  Auto-detection keeps one
    code path: compiled on a TPU backend, interpreted everywhere else.
    """
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Resolve an ``interpret`` knob: ``None`` means auto-detect."""
    return default_interpret() if interpret is None else bool(interpret)


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed here.  Otherwise the cache goes to the fixed
    directory ``<repo>/.jax_cache``.  For entry-point scripts; tests do
    not call it.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_REPO_CACHE_DIR))
    return str(_REPO_CACHE_DIR)
