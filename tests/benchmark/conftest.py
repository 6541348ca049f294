"""The benchmark's tests rehearse every cell on the CPU several times over,
each in a server of its own: the same tiny programs - the weight generators,
the image side every rewrite cell shares - compiled again and again (30 of a
rehearsal's 45 s).  JAX's persistent compilation cache holds them between
the cases of a module, for the tests of THIS directory alone and one
directory a worker: `run.py --rehearse` itself still keeps no cache, and
nothing of a CPU program is ever measured.
"""

import os

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_OPTIONS = {"jax_persistent_cache_min_compile_time_secs": 0,
            "jax_persistent_cache_min_entry_size_bytes": -1}


@pytest.fixture(scope="module", autouse=True)
def cpu_compile_cache():
    """The cache on while a module of this directory runs, and as it was
    afterwards (the tests that compile for a described TPU must not find it
    on: they could write entries no process can read back)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    worker = os.environ.get("PYTEST_XDIST_WORKER", "main")
    options = dict(_OPTIONS, jax_compilation_cache_dir=os.path.join(
        _ROOT, "benchmark", ".jax_cache", f"cpu-tests-{worker}"))
    before = {name: getattr(jax.config, name) for name in options}
    for name, value in options.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()
    yield
    for name, value in before.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()
