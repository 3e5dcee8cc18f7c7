"""Core crypto interfaces: PubKey / PrivKey / BatchVerifier.

Behavior parity: reference crypto/crypto.go:22-54 (interfaces) and
crypto/tmhash (SHA-256 with 20-byte truncated addresses). Addresses are
SHA256(pubkey_bytes)[:20] for ed25519 (reference crypto/ed25519/ed25519.go:180).
"""

from __future__ import annotations

import hashlib
import threading
import time
from abc import ABC, abstractmethod


def tmhash(data: bytes) -> bytes:
    """SHA-256 (reference crypto/tmhash/hash.go:9-11)."""
    return hashlib.sha256(data).digest()


def tmhash20(data: bytes) -> bytes:
    """First 20 bytes of SHA-256 (reference crypto/tmhash TruncatedSize)."""
    return tmhash(data)[:20]


class PubKey(ABC):
    @abstractmethod
    def address(self) -> bytes: ...

    @abstractmethod
    def bytes(self) -> bytes: ...

    @abstractmethod
    def verify_signature(self, msg: bytes, sig: bytes) -> bool: ...

    @abstractmethod
    def type_tag(self) -> str: ...

    def __eq__(self, other):
        return (
            isinstance(other, PubKey)
            and self.type_tag() == other.type_tag()
            and self.bytes() == other.bytes()
        )

    def __hash__(self):
        return hash((self.type_tag(), self.bytes()))


class PrivKey(ABC):
    @abstractmethod
    def sign(self, msg: bytes) -> bytes: ...

    @abstractmethod
    def pub_key(self) -> PubKey: ...

    @abstractmethod
    def bytes(self) -> bytes: ...

    @abstractmethod
    def type_tag(self) -> str: ...


class BatchVerifier(ABC):
    """Accumulate (pubkey, msg, sig) triples, then verify all at once.

    Matches the reference semantics (crypto/crypto.go:41-54): Add may fail
    fast on malformed input; Verify returns (all_valid, per_sig_validity).
    """

    @abstractmethod
    def add(self, pub_key: PubKey, msg: bytes, sig: bytes) -> bool: ...

    @abstractmethod
    def verify(self) -> tuple[bool, list[bool]]: ...


class HostLeg:
    """A host engine's verification in flight on a worker thread, behind
    the pending interface of ed25519's device handles (prefetch() /
    result()). The engines are one ctypes call each, which releases the
    GIL: launched before the caller packs and launches its device batch,
    they run under it and beside each other (types/validation.py). A
    thread a leg, started here and joined by result(): no pool to keep
    across a fork. `own_s` is the call's wall time on its thread."""

    __slots__ = ("_thread", "_out", "_err", "own_s")

    def __init__(self, fn):
        self._out = self._err = None
        self.own_s = 0.0
        self._thread = threading.Thread(
            target=self._run, args=(fn,), name="host-leg", daemon=True)
        self._thread.start()

    def _run(self, fn) -> None:
        t0 = time.perf_counter()
        try:
            self._out = fn()
        except BaseException as e:  # noqa: BLE001 — re-raised by result()
            self._err = e
        self.own_s = time.perf_counter() - t0

    def prefetch(self) -> None:
        pass  # nothing to fetch: the verdict is made on the host

    def result(self):
        self._thread.join()
        if self._err is not None:
            raise self._err
        return self._out
