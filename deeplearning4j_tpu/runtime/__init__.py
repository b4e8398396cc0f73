"""Native runtime tier: C++ data-loader + prefetcher behind ctypes.

The compute path is XLA (no native math needed — SURVEY.md §2.3/§2.9); this
package is the native *runtime around it*, mirroring how the reference rides
on out-of-tree native code for its hot host paths. Falls back to pure Python
when the toolchain is absent, exactly like the reference's reflective
cuDNN-helper fallback (ConvolutionLayer.java:69-79).
"""

from .native_loader import (
    NativeDataSetIterator,
    native_available,
    native_csv_read,
    native_idx_read,
)
from .checkpoint import CheckpointCorruptError, CheckpointStore
from .compile_manager import (
    CompileManager,
    get_compile_manager,
    persistent_cache_dir,
    resolve_persistent_cache,
)
from .inference import canonicalize_input, fast_path_enabled
from .resilience import (
    CircuitBreaker,
    Deadline,
    DeadlinePolicy,
    RetryPolicy,
    resilience_stats,
)
from .online import OnlineTrainer, get_online_trainers

__all__ = [
    "CheckpointCorruptError",
    "CheckpointStore",
    "CircuitBreaker",
    "CompileManager",
    "Deadline",
    "DeadlinePolicy",
    "NativeDataSetIterator",
    "OnlineTrainer",
    "RetryPolicy",
    "canonicalize_input",
    "fast_path_enabled",
    "get_compile_manager",
    "get_online_trainers",
    "native_available",
    "native_csv_read",
    "native_idx_read",
    "persistent_cache_dir",
    "resilience_stats",
    "resolve_persistent_cache",
]
