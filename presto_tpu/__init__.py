"""presto_tpu — a TPU-native distributed SQL query engine.

A ground-up reimagining of a coordinator/worker SQL engine (reference:
Presto, see SURVEY.md) around the XLA execution model:

- Columnar "Pages" of "Blocks" (reference: presto-spi/.../spi/Page.java:34)
  become fixed-shape device arrays with validity masks (`presto_tpu.batch`).
- The interpreted per-page operator loop (reference:
  presto-main/.../operator/Driver.java:347) becomes whole-fragment
  jit-compiled XLA programs (`presto_tpu.exec`).
- JVM bytecode codegen (reference: presto-bytecode, sql/gen/) becomes JAX
  tracing (`presto_tpu.functions`, `presto_tpu.exec.compiler`).
- HTTP shuffle exchanges (reference: execution/buffer/, ExchangeClient)
  become ICI collectives under shard_map (`presto_tpu.parallel`).
"""

import jax

# The engine's BIGINT/DOUBLE are 64-bit end to end (reference: long/double
# Blocks); must be set before any jnp array is created.
jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache + the engine-level executable memo
# (exec/compile_cache.py): the analog of the reference's codegen cache
# (presto-main/.../sql/gen/PageFunctionCompiler.java memoizes compiled
# projections/filters; compiled classes are reused across queries).  XLA
# compiles a whole fragment per (query shape, sf) — at SF100 a single
# compile runs tens of minutes, so cold costs must be paid once per
# machine, not once per process.  Dir from JAX_COMPILATION_CACHE_DIR
# where set (JAX reads it, nothing is set in code), else the
# compile_cache_dir session property (re-checked per query),
# PRESTO_TPU_COMPILE_CACHE (=0 disables) or <checkout>/.jax_cache.
from presto_tpu.exec import compile_cache as _compile_cache  # noqa: E402

_compile_cache.configure()

from presto_tpu.session import Session, connect  # noqa: E402

__version__ = "0.1.0"

__all__ = ["Session", "connect", "__version__"]
