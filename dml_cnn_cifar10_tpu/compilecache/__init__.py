"""Persistent compilation cache + AOT warm-start (docs/COMPILECACHE.md).

Every compile seam in the framework — the train step/chunk, state init,
the eval steps, the serving buckets, the FLOPs probes — can route
through one disk-backed, fail-open executable cache, so supervisor
restarts, elastic world-shrink re-entries, and serve bucket warmups pay
XLA's retrace+compile cost once per program instead of once per process.
"""

from dml_cnn_cifar10_tpu.compilecache.cache import (CachedFunction,
                                                    CompileCache,
                                                    arm_native_cache,
                                                    mesh_context,
                                                    wrap)

__all__ = ["CompileCache", "CachedFunction", "arm_native_cache",
           "mesh_context", "wrap"]
