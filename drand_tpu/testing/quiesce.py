"""Event-loop quiescence that sees work on worker threads.

A fake-clock test moves time only when the simulated system has finished
reacting to the last step. Yielding to the event loop is not enough: the
handlers hand pairing-class work to ``asyncio.to_thread``, and a
verification still running on a worker thread when time moves lands in
the wrong round (a node then waits out a catch-up breather that nothing
ever advances). :class:`CountingExecutor`, installed as the loop's
default executor, counts those jobs; :func:`quiesce` waits until none is
queued or running and the loop has run everything their results woke.

``FakeClock.advance`` quiesces before and after every wake target, so
harnesses that drive time through it (``BeaconTestNetwork``, the daemon
tests, the chaos simulator) share this one wait; :func:`wait_for`
quiesces before each look at the state a test waits for.
"""

from __future__ import annotations

import asyncio
import weakref
from collections.abc import Callable
from concurrent.futures import ThreadPoolExecutor

_EXECUTORS: weakref.WeakKeyDictionary = weakref.WeakKeyDictionary()


class CountingExecutor(ThreadPoolExecutor):
    """A thread pool that counts jobs submitted from its event loop.

    A job counts as busy until the loop has taken its result: the
    decrement is registered on the job's future one loop step after
    ``run_in_executor`` chained the asyncio future to it, so it is
    queued on the loop behind that result."""

    def __init__(self, loop: asyncio.AbstractEventLoop, **kwargs):
        super().__init__(**kwargs)
        self._loop = loop
        self.busy = 0
        self.generation = 0  # submissions + completions, ever

    def submit(self, fn, /, *args, **kwargs):
        fut = super().submit(fn, *args, **kwargs)
        self._bump(1)
        self._loop.call_soon(fut.add_done_callback, self._finished)
        return fut

    def _finished(self, _fut) -> None:
        self._loop.call_soon_threadsafe(self._bump, -1)

    def _bump(self, delta: int) -> None:
        self.busy += delta
        self.generation += 1


def install(loop: asyncio.AbstractEventLoop) -> CountingExecutor:
    """Make a :class:`CountingExecutor` the loop's default executor
    (idempotent); returns it."""
    ex = _EXECUTORS.get(loop)
    if ex is None:
        ex = CountingExecutor(loop, thread_name_prefix="quiesce")
        loop.set_default_executor(ex)
        _EXECUTORS[loop] = ex
    return ex


async def quiesce(yields: int = 25, timeout: float = 60.0) -> None:
    """Return once no worker-thread job is queued or running and a pass
    of ``yields`` loop steps started none; installs the counting
    executor on first use. ``timeout`` (real seconds) bounds the wait
    for a job that never ends."""
    loop = asyncio.get_running_loop()
    ex = install(loop)
    deadline = loop.time() + timeout
    while True:
        mark = ex.generation
        for _ in range(yields):
            await asyncio.sleep(0)
        if ex.busy == 0 and ex.generation == mark:
            return
        while ex.busy and loop.time() < deadline:
            await asyncio.sleep(0.001)
        if loop.time() >= deadline:
            return


async def wait_for(done: Callable[[], bool], what: Callable[[], str],
                   timeout: float = 30.0) -> None:
    """Quiesce until ``done()`` holds; raise ``TimeoutError`` naming
    ``what()`` after ``timeout`` real seconds."""
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout
    while True:
        await quiesce()
        if done():
            return
        if loop.time() > deadline:
            raise TimeoutError(what())
        await asyncio.sleep(0.01)
