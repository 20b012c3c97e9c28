"""horovod_tpu.compile — the compile-once runtime (docs/compile.md).

Two layers: JAX's persistent compilation cache armed from ``hvd.init``
(:func:`arm_persistent_cache`), and the framework-level executable
registry (:func:`get_or_compile`) whose serialized-executable entries
let warm reruns, autotune replays, and restarted elastic workers skip
lowering + compile entirely. :func:`precompile` is the public AOT
warm-pool entry point (``hvd.precompile``).
"""

from .cache import (CompileResult, arm_persistent_cache, cache_dir,
                    clear_memory, compile_count, enabled, executable_key,
                    get_or_compile, persistent_cache_disabled,
                    reset_stats, stats)
from .aot import precompile

__all__ = [
    "CompileResult",
    "arm_persistent_cache",
    "cache_dir",
    "clear_memory",
    "compile_count",
    "enabled",
    "executable_key",
    "get_or_compile",
    "persistent_cache_disabled",
    "precompile",
    "reset_stats",
    "stats",
]
