"""spark_rapids_tpu: a TPU-native columnar SQL accelerator.

A ground-up TPU re-design of the capability set of NVIDIA's RAPIDS
Accelerator for Apache Spark (reference: /root/reference, v21.06):

- a columnar data plane of accelerator-resident batches
  (ref: sql-plugin/.../GpuColumnVector.java) built on JAX arrays with
  static padded shapes, validity masks, and fixed-width string encoding;
- an expression + operator library executing as XLA programs
  (ref: GpuExpressions.scala, basicPhysicalOperators.scala);
- a plan-rewriting engine that tags every operator supported/unsupported
  and falls back to a CPU reference engine per-subtree
  (ref: GpuOverrides.scala, RapidsMeta.scala);
- a tiered HBM -> host -> disk spill store (ref: RapidsBufferStore.scala);
- partitioned shuffle exchanges over jax.sharding Mesh collectives
  (ref: shuffle-plugin UCX transport, GpuShuffleExchangeExec.scala).

Unlike the reference, which plugs into Spark's JVM, this package ships its
own small DataFrame/plan frontend plus a CPU engine (pyarrow-backed) that
plays the role of "CPU Spark" for differential testing and fallback.
"""

__version__ = "0.1.0"

# SQL semantics demand real int64/float64 (Spark's BIGINT/DOUBLE); JAX
# defaults to 32-bit, so importing this package enables the process-global
# x64 flag.  This is a deliberate, documented side effect — the framework
# owns the process the way a Spark executor plugin owns its JVM.  Embedders
# co-hosting f32 JAX models can opt out by setting
# SPARK_RAPIDS_TPU_NO_X64=1 before import (device columns then degrade to
# 32-bit physical types and the parity test suite will not pass).
import os as _os

import jax as _jax

if _os.environ.get("SPARK_RAPIDS_TPU_NO_X64", "") != "1":
    _jax.config.update("jax_enable_x64", True)


def compile_cache_dir() -> str:
    """Where JAX's persistent compilation cache lives: the ONE place
    that decides it.  A directory named by JAX_COMPILATION_CACHE_DIR
    wins, and then nothing in this package calls
    jax.config.update("jax_compilation_cache_dir", ...) — JAX reads the
    variable itself.  Otherwise it is <checkout>/.jax_cache, computed
    from this package's own location: the path is part of the cache's
    key, so a directory that moves between runs never hits."""
    placed = _os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    return _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        ".jax_cache")


if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    # an unwritable checkout raises: a process that silently compiles
    # everything again on every start is not what anybody asked for
    _os.makedirs(compile_cache_dir(), exist_ok=True)
    _jax.config.update("jax_compilation_cache_dir", compile_cache_dir())

from spark_rapids_tpu.config import TpuConf, get_conf, set_conf  # noqa: F401
