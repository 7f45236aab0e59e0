"""Compile counting and device memory, copied from ``chip_smoke.py``."""
from __future__ import annotations

import jax


class CompileClock:
    """Seconds and count of XLA backend compiles since ``reset``."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def reset(self) -> None:
        self.seconds = 0.0
        self.count = 0


def peak_bytes() -> int:
    """Peak bytes in use on the fullest device this process touched."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.local_devices()]
    return max(peaks) if peaks else 0
