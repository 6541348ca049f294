"""AOT executable store activation hook + runtime fingerprint.

Twin of `utils/chaos.py`, for the same layering reason: the LOW layer
(`parallel/runner.py`) builds the compiled denoise programs, but the
store that persists them (`serve/aotcache.py`) lives in the serving
subsystem — the runner must be able to ask "is a store active for the
build I am inside?" without importing serve.  `ExecutorCache` wraps
each executor build in `aot_activation(store, key.short())`, and
`DenoiseRunner.compiled_handle` captures the active (store, scope) pair
exactly where it consults `active_fault_plan()`: a later first dispatch
then deserializes instead of compiling on hit, or compiles and persists
on miss.

The activation is THREAD-LOCAL, not process-global (unlike the chaos
plan): a fleet start compiles many replicas' warmup keys in parallel
threads, and a global scope would stamp one replica's ExecKey onto
another's programs.  Each build thread sees exactly its own activation,
and the scope travels inside the objects the build creates.

The hook stores the store opaquely (anything with ``fingerprint`` /
``load_executable`` / ``save_executable``); no cache semantics live
here.  Production code without an `aot_cache` config block never
activates one; `active_aot_scope()` returning None is the steady state.

`runtime_fingerprint()` is the version half of every cache key: a
serialized executable is only provably "the program that would have
been compiled here" under the same jax/jaxlib/backend, so the store
bakes these fields into the envelope header and rejects on any skew.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Iterator, Optional, Tuple

_TLS = threading.local()


@contextlib.contextmanager
def aot_activation(store: Any, scope: str) -> Iterator[None]:
    """Activate ``store`` for builds on THIS thread, tagged ``scope``
    (the ExecKey.short() compile identity).  Nests: the innermost
    activation wins, the previous one is restored on exit."""
    prev = getattr(_TLS, "active", None)
    _TLS.active = (store, str(scope))
    try:
        yield
    finally:
        _TLS.active = prev


def active_aot_scope() -> Optional[Tuple[Any, str]]:
    """The (store, scope) pair active on this thread, or None."""
    return getattr(_TLS, "active", None)


def runtime_fingerprint() -> Dict[str, str]:
    """jax/jaxlib/backend identity of THIS process — the invalidation
    boundary for persisted executables.  Lazy jax import keeps this
    module a stdlib-only leaf at import time (same rule as chaos.py)."""
    import jax
    import jaxlib

    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "backend": jax.default_backend()}
