"""Fault: half of each catch-up span is left out of verification; the
rounds of the second half take the verdict of the first half as a whole."""


def plant():
    import numpy as np

    from drand_tpu.crypto import batch

    orig = batch.verify_beacons

    def verify_beacons(pubkey, beacons, *a, **kw):
        half = max(1, len(beacons) // 2)
        first = orig(pubkey, beacons[:half], *a, **kw)
        rest = np.full(len(beacons) - half, bool(first.all()))
        return np.concatenate([first, rest])

    batch.verify_beacons = verify_beacons
