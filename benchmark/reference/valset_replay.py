"""The plain reference of the configuration `catchup-1000v-churn`: the
validator set evolved by hand, and one commit judged against it.

What the deployment promises is that every commit is judged against exactly
the set the chain's state gives for its height, with that set's powers. So
the reference keeps its own set: from the genesis members and the `val:<hex
pubkey>=<power>` transactions of each block (upstream abci/example/kvstore)
it applies a block's updates two heights on (FinalizeBlock.validator_updates
of height H are in force at H+2), drops a member whose power is 0, sorts by
power descending and then address, and hashes the set as upstream's
ValidatorSet.Hash does (RFC 6962 Merkle root over each member's
SimpleValidator encoding: key and power). A commit is judged lane by lane,
one signature at a time, with the tally over that set: accepted, or refused
with the first bad index, or refused for power.

It knows no window, no batch and no cache, and imports nothing of the
program: a caller hands it bytes (a block's transactions; a commit's slots
as (flag, address, sign bytes, signature), decoded by whatever reads the
store). A lane that OpenSSL's Ed25519 accepts is accepted (its cofactorless
equation on a canonical key implies ZIP-215's); every other lane, and every
lane where OpenSSL is missing, is judged by the pure-Python ZIP-215
verification beside this file (about 5 ms a signature).
"""

from __future__ import annotations

import functools
import hashlib

from . import ed25519_zip215 as ref

ABSENT, COMMIT, NIL = 1, 2, 3  # BlockIDFlag of a commit's slot
VAL_PREFIX = b"val:"


def address(pub: bytes) -> bytes:
    return hashlib.sha256(pub).digest()[:20]


def _order(members: dict) -> list[tuple[bytes, int]]:
    return sorted(members.items(), key=lambda m: (-m[1], address(m[0])))


def val_updates(txs) -> list[tuple[bytes, int]]:
    """(pubkey, power) of every well-formed validator transaction of one
    block, in the block's order."""
    out = []
    for tx in txs:
        key, eq, value = bytes(tx).partition(b"=")
        if not eq or not key.startswith(VAL_PREFIX):
            continue
        try:
            out.append((bytes.fromhex(key[len(VAL_PREFIX):].decode()),
                        int(value)))
        except ValueError:
            continue
    return out


def evolve(first: int, set_first, set_second, updates_at: dict,
           tip: int) -> dict:
    """{height: [(pubkey, power)] in the set's order} for first..tip+2.
    `set_first` judges height `first` and `set_second` height first+1 (at
    genesis both are the genesis set); updates_at[h] are the updates block h
    carries (val_updates), in force at h+2."""
    sets = {first: _order(dict(set_first)), first + 1: _order(dict(set_second))}
    for h in range(first, tip + 1):
        members = dict(sets[h + 1])
        for pub, power in updates_at.get(h, ()):
            if power == 0:
                del members[pub]  # KeyError: the chain removes a stranger
            else:
                members[pub] = power
        sets[h + 2] = _order(members)
    return sets


def last_changed(first: int, updates_at: dict, tip: int) -> int:
    """The state's last_height_validators_changed after block `tip`."""
    hs = [h for h in range(first, tip + 1) if updates_at.get(h)]
    return max(hs) + 2 if hs else first


def _varint(n: int) -> bytes:
    out = bytearray()
    while n > 0x7F:
        out.append(n & 0x7F | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _simple_validator(pub: bytes, power: int) -> bytes:
    # SimpleValidator{1: PublicKey{1: ed25519 bytes}, 2: voting_power}
    key = b"\x0a" + _varint(len(pub)) + pub
    return b"\x0a" + _varint(len(key)) + key + b"\x10" + _varint(power)


def _merkle(leaves: list[bytes]) -> bytes:
    if not leaves:
        return hashlib.sha256(b"").digest()
    if len(leaves) == 1:
        return hashlib.sha256(b"\x00" + leaves[0]).digest()
    k = 1 << (len(leaves) - 1).bit_length() - 1  # largest power of 2 < n
    return hashlib.sha256(
        b"\x01" + _merkle(leaves[:k]) + _merkle(leaves[k:])).digest()


def set_hash(members) -> bytes:
    return _merkle([_simple_validator(pub, power) for pub, power in members])


@functools.lru_cache(maxsize=4096)
def _openssl_key(pub: bytes):
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PublicKey,
    )

    return Ed25519PublicKey.from_public_bytes(pub)


def verify_lane(pub: bytes, msg: bytes, sig: bytes) -> bool:
    try:
        from cryptography.exceptions import InvalidSignature

        key = _openssl_key(pub)
    except (ImportError, ValueError):  # no OpenSSL, or a key it will not load
        return ref.verify(pub, msg, sig)
    try:
        key.verify(sig, msg)
        return True
    except (InvalidSignature, ValueError):
        return ref.verify(pub, msg, sig)


def judge(members, slots):
    """One commit against one set. slots: [(flag, address, sign bytes,
    signature)] in slot order. Every non-absent lane is judged and COMMIT
    power counted. Returns
    ("accepted",), ("size", n), ("address", index), ("signature", index)
    with the first bad index, or ("power", tallied, needed_over)."""
    if len(slots) != len(members):
        return ("size", len(slots))
    tallied = 0
    for i, ((pub, power), (flag, addr, msg, sig)) in enumerate(
            zip(members, slots)):
        if flag == ABSENT:
            continue
        if addr != address(pub):
            return ("address", i)
        if not verify_lane(pub, msg, sig):
            return ("signature", i)
        if flag == COMMIT:
            tallied += power
    needed = sum(power for _, power in members) * 2 // 3
    if tallied <= needed:
        return ("power", tallied, needed)
    return ("accepted",)


def signed(flags) -> int:
    """Non-absent signatures of one commit, from its slots' flags."""
    return sum(1 for flag in flags if flag != ABSENT)
