"""Shared utilities: config loading, loggers, profiling."""

from .cache import LRUCache, default_cache_dir, enable_compile_cache
from .config import load_yaml_config, merge_config_into_args
from .logging import (ProgressPrinter, ScalarWriter, TableLogger, TSVLogger,
                      format_validation_line)
from .profiling import StepProfiler

# graph re-exports are lazy (PEP 562): utils.graph imports flax+jax, and
# `import cpd_tpu.utils` must stay stdlib-cheap so CLIs can parse config
# and set JAX env vars before jax ever loads (see cpd_tpu/__init__.py).
_GRAPH_NAMES = ("GraphModule", "GraphClassifier", "build_graph", "rel_path",
                "union", "path_iter")

__all__ = ["load_yaml_config", "merge_config_into_args", "TableLogger",
           "TSVLogger", "ScalarWriter", "ProgressPrinter",
           "format_validation_line", "enable_compile_cache",
           "default_cache_dir", "LRUCache", "StepProfiler",
           *_GRAPH_NAMES]


def __getattr__(name):
    if name in _GRAPH_NAMES:
        from . import graph

        return getattr(graph, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
