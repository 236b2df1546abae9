"""What the harness reads from jax itself: compile seconds and counts
(``jax.monitoring``), device memory, and the host clock.  Copied in spirit
from ``chip_smoke.py`` (``CompileClock``, ``hbm_line``) so that later changes
to that file cannot move the yardstick."""

from __future__ import annotations

import gc
import time

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


def now() -> float:
    """Host clock, seconds.  Differences under ~250 ms are not trusted
    anywhere in the harness."""
    return time.perf_counter()


class CompileClock:
    """Seconds jax spent in backend compiles (persistent-cache reads
    included), how many programs, and how many came from the cache."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == BACKEND_COMPILE:
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == CACHE_HIT:
            self.cache_hits += 1


def peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest device.  A backend that reports
    no memory stats (the CPU) gives 0, which only a test ever sees."""
    gc.collect()
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return max(peaks)


def tree_bytes(tree) -> int:
    import jax

    return sum(int(x.nbytes) for x in jax.tree.leaves(tree)
               if hasattr(x, "nbytes"))
