"""spark-rapids-tpu: a TPU-native columnar SQL execution framework.

Re-design of the RAPIDS Accelerator for Apache Spark (NVIDIA/spark-rapids @ v0.3.0)
for TPU: plan-rewrite engine -> columnar TpuExec operators -> jitted jax/XLA programs
over padded Arrow-layout device buffers -> mesh/ICI shuffle. See SURVEY.md (reference
blueprint) and DESIGN.md (TPU-first decisions).
"""

import jax

# Spark SQL semantics require 64-bit longs/doubles; jax defaults to 32-bit.
jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: fused-stage programs (sort-based
# group-bys especially) take tens of seconds each to compile for the
# chip, and every fresh process would otherwise pay that again. ONE
# helper decides the directory (JAX_COMPILATION_CACHE_DIR, else a
# session's compile.cacheDir, else a fixed path in the checkout —
# exec/compile_cache.xla_cache_dir); turning the cache off is jax's own
# jax_enable_compilation_cache. Setting
# spark.rapids.tpu.sql.compile.cacheDir adds the managed layer — engine
# signature index, cold-vs-disk classification, prewarm corpus
# (exec/compile_cache.py, docs/compile.md).
from .exec.compile_cache import (  # noqa: E402
    point_xla_cache as _point_xla_cache, xla_cache_dir as _xla_cache_dir)
_point_xla_cache(_xla_cache_dir())

__version__ = "0.1.0"

from .config import TpuConf  # noqa: E402,F401
from .columnar import dtypes  # noqa: E402,F401
from .columnar.batch import ColumnarBatch  # noqa: E402,F401
from .columnar.column import Column, Scalar  # noqa: E402,F401


def __getattr__(name):
    # lazy: importing the api pulls in the full plan/exec stack
    if name == "TpuSession":
        from .api.session import TpuSession
        return TpuSession
    if name == "functions":
        from .api import functions
        return functions
    raise AttributeError(name)
