"""Caching utilities: the persistent XLA compilation cache switch, and a
small bounded LRU mapping for host-side jit-callable caches.

Full-model train steps cost tens of seconds of XLA compile; caching them
makes a second run on the same chip start in seconds.  Every entry point
that compiles for the chip (trainer CLIs, bench.py, chip_smoke.py,
tools/pallas_check.py, tools/bench_serve.py) calls `enable_compile_cache`
so the cache-dir logic lives in exactly one place.
"""

from __future__ import annotations

import os
from collections import OrderedDict
from typing import Any, Callable, Hashable

__all__ = ["enable_compile_cache", "default_cache_dir", "LRUCache"]


class LRUCache:
    """Bounded insertion/recency-ordered mapping for host-side caches of
    jitted callables (e.g. parallel/dist.py `make_sum_gradients_fn`, keyed
    by treedef).  A plain dict there grows without bound when callers keep
    presenting new pytree structures; evicting the least-recently-used
    entry just drops a compiled callable — the next call with that
    structure re-traces, which is a cost, never an error."""

    def __init__(self, maxsize: int):
        if maxsize < 1:
            raise ValueError(f"maxsize must be >= 1, got {maxsize}")
        self.maxsize = maxsize
        self._d: OrderedDict = OrderedDict()

    def get_or_create(self, key: Hashable, create: Callable[[], Any]) -> Any:
        """Return the cached value for `key`, creating (and inserting) it
        via `create()` on a miss; either way `key` becomes most-recent."""
        if key in self._d:
            self._d.move_to_end(key)
            return self._d[key]
        value = create()
        self._d[key] = value
        while len(self._d) > self.maxsize:
            self._d.popitem(last=False)
        return value

    def __len__(self) -> int:
        return len(self._d)

    def __contains__(self, key) -> bool:
        return key in self._d


def default_cache_dir() -> str:
    """<checkout>/.jax_cache (checkout = parent of the cpd_tpu package).
    A FIXED path: the directory is part of jax's cache key, so one that
    moved between runs could never hit."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def enable_compile_cache() -> None:
    """Turn on jax's persistent compilation cache for this process.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax has already read it
    and no directory is set in code — the cache can be placed from
    outside.  Otherwise it lives at `default_cache_dir()`.  Call after
    the platform is configured: the resolved backend is consulted, which
    initializes it.

    A no-op on the CPU backend: reloading XLA:CPU AOT executables that
    contain collectives gave deserialized modules conflicting rendezvous
    op_ids and F-aborted the process (rc=-6) on warm re-runs of the
    8-virtual-device dryrun.  Observed on the jaxlib this tree was
    written against; not re-checked on 0.9.0, and a CPU compile of the
    test-sized models is cheap enough not to need the cache."""
    import jax

    if jax.default_backend() == "cpu":
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", default_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
