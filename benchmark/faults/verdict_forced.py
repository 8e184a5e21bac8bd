"""Fault: the dispatch layer's catch-up verdicts are altered where they
are produced: every round of a span reported valid."""


def plant():
    import numpy as np

    from drand_tpu.crypto import batch

    orig = batch.verify_beacons

    def verify_beacons(pubkey, beacons, *a, **kw):
        orig(pubkey, beacons, *a, **kw)
        return np.ones(len(beacons), dtype=bool)

    batch.verify_beacons = verify_beacons
