"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points that compile at real size (`chip_smoke.py`, the
`benchmarks/` scripts) call `use_compile_cache()` once, before their first
compile, so a second run of the same program reads its executables back
instead of compiling them again.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory and
    return that directory.

    With `JAX_COMPILATION_CACHE_DIR` set, JAX already reads it and nothing
    is set here. Otherwise the cache goes to `<checkout>/.jax_cache`: a
    fixed path, never a temporary, per-process or per-run one, because a
    directory that moves between runs never hits.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
