"""The two decisions that depend on which backend jax resolved.

Interpret mode exists so the CPU test tier can execute the Pallas kernel
bodies; it is never a fallback.  A kernel called on a TPU is compiled by
Mosaic, and a backend that is neither is an error — so a machine whose
chip failed to come up cannot quietly interpret its way to a passing run.
Likewise a program that measures the device (`chip_smoke.py`, `bench.py`,
`tools/pallas_check.py`) refuses any other backend: an unset
`JAX_PLATFORMS` on a machine without a chip resolves to `cpu` silently.
"""

from __future__ import annotations

import os
import sys

import jax

__all__ = ["interpret_mode", "require_tpu"]


def interpret_mode() -> bool:
    """True on the CPU backend, False on TPU, an error anywhere else."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels here target TPU (compiled) or CPU (interpreted, "
        f"tests only); the resolved jax backend is {backend!r}")


def require_tpu(program: str) -> list:
    """`jax.devices()` when they are TPUs; otherwise one line on stderr
    naming the platform and exit code 2 — before anything is computed."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"{program}: needs a TPU, but jax resolved platform "
              f"{devices[0].platform!r} (JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS')!r}); refusing to run",
              file=sys.stderr)
        raise SystemExit(2)
    return devices
