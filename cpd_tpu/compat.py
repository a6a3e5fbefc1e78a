"""The one home of ``jax.experimental`` imports, for the installed jax 0.9.

Everything in cpd_tpu (and its tests/tools) imports ``shard_map``, the
Pallas namespaces, ``multihost_utils`` and the stock flash kernel from
here, so the ``compat-drift`` lint rule (docs/ANALYSIS.md) can flag every
``jax.experimental`` use outside this file — when upstream promotes or
renames one of them, exactly one file changes.

Stdlib-cheap rule: this module DOES import jax, so it must never be
imported from ``cpd_tpu/__init__.py`` eagerly (see the lazy-export note
there) — only from the L1/L2 modules that already depend on jax.
"""

from __future__ import annotations

from jax import shard_map
from jax.experimental import multihost_utils, pallas
from jax.experimental.pallas import tpu as pallas_tpu

__all__ = ["shard_map", "pallas", "pallas_tpu", "multihost_utils",
           "flash_attention_import", "megablox_gmm_import"]


def flash_attention_import():
    """The stock Pallas TPU flash-attention kernel, resolved lazily.

    Returns the ``flash_attention`` callable.  Lazy because importing
    the kernel module is heavyweight and TPU-flavored; callers
    (ops/attention.py's ``impl="flash"`` path) only reach it when the
    user explicitly asks for the stock kernel."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention)
    return flash_attention


def megablox_gmm_import():
    """jax's megablox grouped matrix product (Pallas TPU; `gmm` with its
    `tgmm` backward under a `custom_vjp`), resolved lazily like the flash
    kernel.  `ops/grouped.py` is its one caller."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    return gmm
