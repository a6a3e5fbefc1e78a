"""The program registry: where subsystems declare contract-bearing
compiled programs for the jaxpr-level rules.

A declaration is a `ProgramSpec`: a ``build()`` thunk returning
``(fn, args)`` — ``fn`` is traced with ``jax.make_jaxpr(fn)(*args)``
(args are ``ShapeDtypeStruct``s; nothing executes) — plus the program's
CONTRACTS (which rules gate it) and its source ``deps`` (the modules
whose edits invalidate its cached facts).  Subsystem modules export an
``ir_programs(reg)`` function; `collect_programs` imports the provider
list and gathers every declaration.  Import stays stdlib-only — jax is
touched only inside ``build()`` at trace time (trace.py).

Contracts a spec can claim (each enforced by one rule in rules.py):

``twin``           bitwise-parity twin group: every program sharing the
                   group id must move the IDENTICAL multiset of
                   transport collectives (kind, axes, payload
                   dtype/shape, trip count) — `ir-schedule`.
``wire``           zero-arg thunk returning the analytic transport-byte
                   expectation (``ring_transport_bytes`` & co); the
                   jaxpr-counted bytes must equal it — `ir-wire-ledger`.
``bitwise``        the program is bitwise-gated (claims cross-program
                   bit reproducibility somewhere in the suite): no
                   ulp-unstable primitive may appear outside the blessed
                   exact helpers — `ir-bitwise`.
``overlap``        expected interleaving verdict (True: transport
                   collectives must interleave with compute; False:
                   must strictly postdate it) — `ir-overlap`.
``retrace_group`` / ``retrace_key``
                   programs in one group are entries of one StepTable
                   family; two members with DISTINCT traced programs
                   must carry distinct keys (the PR 5 half-keyed
                   StepTable bug, verified dynamically) — `ir-retrace`.
``axis_sizes``     mesh axis name -> size, needed to price all_gather /
                   all_to_all wire bytes per device.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import os
import sys
from typing import Callable, Optional

__all__ = ["ProgramSpec", "ProgramRegistry", "collect_programs",
           "DEFAULT_PROVIDERS", "ensure_cpu_devices", "IR_WORLD"]

# the virtual CPU mesh every declaration sizes against (conftest.py's
# device count; ensure_cpu_devices forces it for the bare CLI)
IR_WORLD = 8

# provider modules collect_programs imports by default — each exports
# ir_programs(reg).  Order is the report order.
DEFAULT_PROVIDERS = (
    "cpd_tpu.parallel.reduction",
    "cpd_tpu.parallel.ring",
    "cpd_tpu.parallel.overlap",
    "cpd_tpu.parallel.zero",
    "cpd_tpu.linalg.blockmm",
    "cpd_tpu.linalg.qr",
    "cpd_tpu.linalg.eigen",
    "cpd_tpu.train.step",
    "cpd_tpu.train.lm",
    "cpd_tpu.serve.model",
)


def ensure_cpu_devices(n: int = IR_WORLD) -> None:
    """Force an n-device virtual CPU platform, BEFORE jax initializes.

    A no-op when jax is already imported (pytest's conftest.py has
    already done this; a host that imported jax with fewer devices will
    surface per-program trace failures instead — the honesty path).
    The analyzer traces on the 8-device CPU mesh whatever the machine
    holds: a TPU host has 1 or 4 devices, not the IR_WORLD the declared
    programs are written for, and a lint run must never take a chip.
    The platform is pinned through ``jax.config`` as well as the env
    var, so a caller's later env edits cannot undo it (the same double
    conftest.py does)."""
    if "jax" in sys.modules:
        return
    import re
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   flags)
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n}").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")


@dataclasses.dataclass(frozen=True)
class ProgramSpec:
    """One declared contract-bearing program (module docstring)."""
    name: str
    build: Callable                       # () -> (fn, args_tuple)
    deps: tuple = ()                      # dotted module names
    axis_sizes: Optional[dict] = None     # {axis_name: size}
    twin: Optional[str] = None
    wire: Optional[Callable] = None       # () -> expected bytes
    bitwise: bool = False
    allow_unstable: tuple = ()            # blessed prim names + reasons
    overlap: Optional[bool] = None
    retrace_group: Optional[str] = None
    retrace_key: Optional[object] = None  # hashable; required with group
    origin: tuple = ("<unknown>", 1)      # (path, line) of the declare

    def __post_init__(self):
        if self.retrace_group is not None and self.retrace_key is None:
            raise ValueError(
                f"program {self.name!r}: retrace_group without a "
                f"retrace_key — the probe compares keys, a keyless "
                f"member would be unverifiable")


class ProgramRegistry:
    """Ordered, name-unique collection of ProgramSpecs."""

    def __init__(self):
        self.specs: list[ProgramSpec] = []
        self._names: set[str] = set()

    def declare(self, name: str, build: Callable, **kw) -> ProgramSpec:
        if name in self._names:
            raise ValueError(f"duplicate program name {name!r}")
        if "origin" not in kw:
            f = sys._getframe(1)
            kw["origin"] = (f.f_code.co_filename, f.f_lineno)
        spec = ProgramSpec(name=name, build=build, **kw)
        self._names.add(name)
        self.specs.append(spec)
        return spec


def _import_provider(entry: str):
    """A provider is a dotted module name or a .py file path (fixture
    registries in tests)."""
    if entry.endswith(".py") or os.sep in entry:
        path = os.path.abspath(entry)
        mod_name = "_cpd_ir_provider_" + os.path.basename(path)[:-3]
        ispec = importlib.util.spec_from_file_location(mod_name, path)
        if ispec is None or ispec.loader is None:
            raise ImportError(f"cannot load provider file {entry}")
        mod = importlib.util.module_from_spec(ispec)
        # registered so dataclasses/pickle introspection inside the
        # provider resolves its module while executing
        sys.modules[mod_name] = mod
        ispec.loader.exec_module(mod)
        return mod
    return importlib.import_module(entry)


def collect_programs(providers=DEFAULT_PROVIDERS) -> ProgramRegistry:
    """Import each provider and gather its declarations.  A provider
    without ``ir_programs`` is a loud error — a silently skipped
    provider would shrink the gate's coverage to whatever still
    declares."""
    reg = ProgramRegistry()
    for entry in providers:
        mod = _import_provider(entry)
        fn = getattr(mod, "ir_programs", None)
        if fn is None:
            raise ValueError(
                f"IR provider {entry!r} has no ir_programs(reg) — "
                f"remove it from the provider list or declare programs")
        fn(reg)
    return reg
