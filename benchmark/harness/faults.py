"""Faults put under the timed path on purpose, to show that `correct` comes
out false (benchmark/tests, and the control runs on the chip). The benchmark's
own runs never use them: run.py takes --fault only beside --seconds, prints
the fault's name on every line that matters and marks the result line.

accept_all  the control: breaks the guarantee "every signature is verified and
            a bad one is refused". Every pending verdict says "all lanes good"
            whatever the engine found, as a verifier that skips lanes would.
host_path   every batch goes to the host engine, as a dispatch that hides the
            device would: the path check reads false from the timed path's
            own counter (test_rehearse.py; on the chip 4 of 4, PERF.md).
"""

from __future__ import annotations


def accept_all() -> None:
    from cometbft_tpu.crypto import ed25519 as E

    for cls in (E.PendingBatch, E.DonePending):
        orig = cls.result

        def result(self, _orig=orig):
            _ok, bits = _orig(self)
            return True, [True] * len(bits)

        cls.result = result


def host_path() -> None:
    from cometbft_tpu.crypto import ed25519 as E

    E.NATIVE_MAX = 1 << 30


FAULTS = {"accept_all": accept_all, "host_path": host_path}
