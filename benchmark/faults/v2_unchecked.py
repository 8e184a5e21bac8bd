"""Control for the catch-up cells: the guarantee that every round's V2
signature verifies is dropped (spans and the head are checked on V1
alone), the step that would halve a walk's signature work."""


def plant():
    import dataclasses

    from drand_tpu.chain import beacon as chain_beacon
    from drand_tpu.client.verify import VerifyingClient
    from drand_tpu.crypto import batch

    orig = batch.verify_beacons

    def verify_beacons(pubkey, beacons, *a, **kw):
        return orig(pubkey, [dataclasses.replace(b, signature_v2=b"")
                             for b in beacons], *a, **kw)

    batch.verify_beacons = verify_beacons
    VerifyingClient._check_sigs = staticmethod(chain_beacon.verify_beacon)
