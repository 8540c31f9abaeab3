"""Small shared utilities: pytree helpers, rng, precision policy, logging."""
from __future__ import annotations

import dataclasses
import logging
import os
import time
from pathlib import Path
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

logger = logging.getLogger("repro")
if not logger.handlers:
    _h = logging.StreamHandler()
    _h.setFormatter(logging.Formatter("[%(asctime)s repro] %(message)s", "%H:%M:%S"))
    logger.addHandler(_h)
    logger.setLevel(logging.INFO)

PyTree = Any

# the checkout's root: src/repro/utils.py -> <checkout>
CHECKOUT = Path(__file__).resolve().parents[2]


def use_compile_cache() -> str:
    """Put JAX's persistent compilation cache in a fixed place; returns
    the directory in use. Call it from a program's ``main()``, never at
    import.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX takes the directory
    from it and nothing is set here. Otherwise the cache goes to
    ``<checkout>/.jax_cache``: the path is part of the cache key, so a
    temporary or per-run directory would never hit."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------------------
# RNG helpers
# ---------------------------------------------------------------------------
def key_iter(seed: int) -> Iterator[jax.Array]:
    """Infinite stream of fresh PRNG keys."""
    key = jax.random.PRNGKey(seed)
    while True:
        key, sub = jax.random.split(key)
        yield sub


def split_dict(key: jax.Array, names: list[str]) -> dict[str, jax.Array]:
    keys = jax.random.split(key, len(names))
    return dict(zip(names, keys))


# ---------------------------------------------------------------------------
# Pytree helpers
# ---------------------------------------------------------------------------
def tree_size(tree: PyTree) -> int:
    """Total number of array elements in a pytree."""
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def tree_bytes(tree: PyTree) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(tree))


def tree_cast(tree: PyTree, dtype) -> PyTree:
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, tree
    )


def tree_zeros_like(tree: PyTree) -> PyTree:
    return jax.tree.map(jnp.zeros_like, tree)


def tree_norm(tree: PyTree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree)]
    return jnp.sqrt(sum(leaves))


def tree_add(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(jnp.add, a, b)


def tree_scale(a: PyTree, s) -> PyTree:
    return jax.tree.map(lambda x: x * s, a)


# ---------------------------------------------------------------------------
# Precision policy
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Policy:
    """Mixed-precision policy: params stored / compute / output dtypes."""

    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    output_dtype: Any = jnp.float32

    def cast_compute(self, tree: PyTree) -> PyTree:
        return tree_cast(tree, self.compute_dtype)


DEFAULT_POLICY = Policy()
FULL_PRECISION = Policy(jnp.float32, jnp.float32, jnp.float32)


# ---------------------------------------------------------------------------
# Timing
# ---------------------------------------------------------------------------
class Timer:
    def __init__(self):
        self.t0 = time.perf_counter()

    def __call__(self) -> float:
        return time.perf_counter() - self.t0


def timed(fn: Callable, *args, n: int = 3, warmup: int = 1, **kw):
    """Best-of-n wall clock for a blocking fn; returns (seconds, last_result)."""
    out = None
    for _ in range(warmup):
        out = fn(*args, **kw)
        jax.block_until_ready(out)
    best = float("inf")
    for _ in range(n):
        t = Timer()
        out = fn(*args, **kw)
        jax.block_until_ready(out)
        best = min(best, t())
    return best, out


def human_bytes(n: float) -> str:
    for unit in ["B", "KiB", "MiB", "GiB", "TiB"]:
        if abs(n) < 1024:
            return f"{n:.2f} {unit}"
        n /= 1024
    return f"{n:.2f} PiB"


def human_count(n: float) -> str:
    for unit in ["", "K", "M", "B", "T"]:
        if abs(n) < 1000:
            return f"{n:.2f}{unit}"
        n /= 1000
    return f"{n:.2f}Q"
