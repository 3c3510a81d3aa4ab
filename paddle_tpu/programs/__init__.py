"""paddle_tpu.programs — the unified persistent program store.

One `ProgramStore` owns the AOT compile of every jitted
compilation tier (jit.TrainStep / to_static, the serving engine's
decode + prefill programs; the eager dispatch cache keeps its own
in-process tier and reports through the same catalog), keyed like the
dispatch cache plus a backend fingerprint, with an optional crash-safe
on-disk tier so a preempted trainer or a cold serving replica restarts
without paying XLA compiles. See store.py for the full contract.

Enable persistence with `programs.configure('/path/to/store')`, the
`FLAGS_program_store_dir` flag/env var, or the examples'
`--program-store` argument; `get_store().preload()` bulk-loads the
matching entries at startup (Model.fit and ReplicaSet do this
automatically when the store is persistent).
"""
from .scopes import SCOPES, scope_path, scope_table
from .store import (PoolIO, ProgramDeserializeError, ProgramStore, StoredJit,
                    backend_fingerprint, code_token, compile_cache_dir,
                    configure, describe_statics, ensure_compile_cache,
                    get_store, store_key)

__all__ = [
    'PoolIO', 'ProgramDeserializeError', 'ProgramStore', 'SCOPES',
    'StoredJit',
    'backend_fingerprint', 'code_token', 'compile_cache_dir', 'configure',
    'describe_statics', 'ensure_compile_cache', 'get_store',
    'scope_path', 'scope_table', 'store_key',
]
