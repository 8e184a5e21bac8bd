"""The fake-clock harness waits for work on worker threads.

A partial verification that is still running on a worker thread when
the clock moves on must not cost the round: ``FakeClock.advance``
quiesces (drand_tpu/testing/quiesce.py) until no executor job is queued
or running before it moves time.
"""

import asyncio
import time

import pytest

from drand_tpu.chain.engine import handler as handler_mod
from drand_tpu.testing.chaos import structural_crypto
from drand_tpu.testing.harness import BeaconTestNetwork

N, T, PERIOD = 3, 2, 5


@pytest.mark.timeout(10)
@pytest.mark.asyncio
async def test_slow_partial_verification_keeps_every_round():
    with structural_crypto():
        verify = handler_mod._verify_partial_packet

        def slow_verify(*args):
            time.sleep(0.2)
            return verify(*args)

        # structural_crypto restores the real function on exit
        handler_mod._verify_partial_packet = slow_verify
        net = BeaconTestNetwork(n=N, t=T, period=PERIOD)
        try:
            await net.start_all()
            await net.advance_to_genesis()
            for _ in range(4):
                await net.clock.advance(PERIOD)
            # genesis is round 1: four periods later every node is at 5,
            # none behind and none ahead
            for i in range(N):
                await net.wait_round(i, 5, timeout=5.0)
            assert [n.store.last().round for n in net.nodes] == [5] * N
        finally:
            net.stop_all()


@pytest.mark.asyncio
async def test_quiesce_waits_for_the_thread_and_what_it_wakes():
    from drand_tpu.testing.quiesce import quiesce

    seen = []

    async def job():
        await asyncio.to_thread(time.sleep, 0.1)
        seen.append("resumed")

    task = asyncio.ensure_future(job())
    await quiesce()
    assert seen == ["resumed"]
    await task
