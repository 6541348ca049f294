"""Every backend compile (or persistent-cache load) JAX performs, by program
name, and the persistent cache's hit / miss events (copied from
chip_smoke.py's CompileLog, PR 21)."""

import jax

_COMPILE = "/jax/core/compile/backend_compile_duration"

# A compile inside the measured window that takes this long is a model
# program (encode, denoise, decode: tens of seconds at published widths, and
# over a second even as a persistent-cache load) being built on the request
# path.  Below it are eager glue ops, counted and printed, not judged.
MODEL_COMPILE_FLOOR_S = 1.0


class CompileLog:
    def __init__(self):
        self.compiles = []  # (fun_name, seconds)
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == _COMPILE:
            self.compiles.append((str(kw.get("fun_name", "?")), seconds))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self) -> int:
        return len(self.compiles)

    def since(self, mark: int):
        return self.compiles[mark:]


def split_model_compiles(events):
    """(model-program compiles, number of small glue-op compiles)."""
    big = [(n, round(s, 2)) for n, s in events if s >= MODEL_COMPILE_FLOOR_S]
    return big, len(events) - len(big)
