"""Injectable clocks — the testing backbone.

Mirrors the reference's pervasive use of jonboulle/clockwork (SURVEY.md §4):
every time-dependent component takes a Clock so multi-node tests advance
rounds deterministically with zero wall-clock waiting
(reference: core/util_test.go:235-257 MoveTime/MoveToTime).
"""

from __future__ import annotations

import asyncio
import heapq
import itertools
import time as _time

from ..testing.quiesce import quiesce


class Clock:
    """Abstract clock: wall time + async sleeping."""

    def now(self) -> float:
        raise NotImplementedError

    async def sleep(self, seconds: float) -> None:
        raise NotImplementedError

    async def sleep_until(self, t: float) -> None:
        delta = t - self.now()
        if delta > 0:
            await self.sleep(delta)


class SystemClock(Clock):
    def now(self) -> float:
        return _time.time()

    async def sleep(self, seconds: float) -> None:
        await asyncio.sleep(max(0.0, seconds))


class FakeClock(Clock):
    """Deterministic clock. Tasks calling ``sleep`` block until ``advance``
    moves time past their wake target. ``advance`` steps through intermediate
    wake targets in order and yields control so woken tasks can run (and
    possibly sleep again within the same window) — matching clockwork's
    semantics that drand's tests rely on."""

    def __init__(self, start: float = 1_700_000_000.0):
        self._now = float(start)
        self._waiters: list[tuple[float, int, asyncio.Future]] = []
        self._counter = itertools.count()

    def now(self) -> float:
        return self._now

    async def sleep(self, seconds: float) -> None:
        if seconds <= 0:
            await asyncio.sleep(0)
            return
        fut = asyncio.get_running_loop().create_future()
        heapq.heappush(self._waiters, (self._now + seconds, next(self._counter), fut))
        await fut

    def next_wake(self) -> float | None:
        """Earliest pending wake target, or None when nothing sleeps.
        Lets harnesses (testing/chaos.py) step time deterministically
        from wake target to wake target instead of jumping a whole
        window — the clock then PARKS between targets, so everything a
        delivery triggers is timestamped at the delivery time."""
        return self._waiters[0][0] if self._waiters else None

    def _wake_due(self) -> bool:
        woke = False
        while self._waiters and self._waiters[0][0] <= self._now:
            _, _, fut = heapq.heappop(self._waiters)
            if not fut.done():
                fut.set_result(None)
                woke = True
        return woke

    async def settle(self, rounds: int = 25) -> None:
        """Let scheduled tasks, and the worker-thread jobs they started,
        run until quiescent (``testing/quiesce.py``)."""
        await quiesce(rounds)

    async def advance(self, seconds: float) -> None:
        """Move time forward, waking sleepers in order of their targets."""
        # let freshly-created tasks run up to their first sleep, so they
        # register waiters BEFORE time moves (otherwise they miss the window)
        await self.settle()
        target = self._now + seconds
        while True:
            next_wake = self._waiters[0][0] if self._waiters else None
            if next_wake is not None and next_wake <= target:
                self._now = max(self._now, next_wake)
                self._wake_due()
                await self.settle()
            else:
                break
        self._now = target
        self._wake_due()
        await self.settle()

    async def advance_to(self, t: float) -> None:
        if t > self._now:
            await self.advance(t - self._now)
