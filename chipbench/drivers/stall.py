"""How long an event loop stalled: the longest lateness of a task that asks
to wake every ``tick`` seconds, and the longest garbage collection."""

from __future__ import annotations

import asyncio
import gc
import time


class StallWatch:
    def __init__(self, tick: float = 0.005):
        self.tick = tick
        self.loop_ms = 0.0
        self.gc_ms = 0.0
        self._gc_t0 = 0.0
        self._task = None

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self.gc_ms = max(self.gc_ms,
                             (time.perf_counter() - self._gc_t0) * 1e3)

    async def _watch(self) -> None:
        loop = asyncio.get_running_loop()
        while True:
            t = loop.time()
            await asyncio.sleep(self.tick)
            self.loop_ms = max(self.loop_ms,
                               (loop.time() - t - self.tick) * 1e3)

    def start(self) -> "StallWatch":
        gc.callbacks.append(self._on_gc)
        self._task = asyncio.ensure_future(self._watch())
        return self

    def stop(self) -> dict:
        self._task.cancel()
        gc.callbacks.remove(self._on_gc)
        return {"loop_stall_ms_max": self.loop_ms, "gc_ms_max": self.gc_ms}
