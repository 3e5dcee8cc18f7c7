"""Validators and the proposer-priority validator set.

Behavior parity with reference types/validator_set.go: ordering by
(voting power desc, address asc), proposer rotation via priority queue
(IncrementProposerPriority :116, rescale window :143, avg-centering :227),
merkle hash over SimpleValidator encodings (:348), and ABCI update
application with the -(P + P/8) new-validator priority penalty (:659).
Arithmetic is int64-clipped exactly like the reference (safeAddClip /
truncated division), since priorities are consensus-visible state.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import NamedTuple

import numpy as np

from ..crypto import merkle
from ..crypto.keys import PubKey
from ..encoding import proto as pb
from ..utils.metrics import state_metrics

I64_MAX = (1 << 63) - 1
I64_MIN = -(1 << 63)
MAX_TOTAL_VOTING_POWER = I64_MAX // 8
PRIORITY_WINDOW_SIZE_FACTOR = 2


# ValidatorSet.encode(): field 1 of the set (a Validator, embedded) and
# field 4 of a Validator (the proposer priority, a varint)
_TAG_VALIDATOR = pb.tag(1, pb.WT_LEN)
_TAG_PRIORITY = pb.tag(4, pb.WT_VARINT)


def _clip(v: int) -> int:
    return max(I64_MIN, min(I64_MAX, v))


def _trunc_div(a: int, b: int) -> int:
    """Go-style int64 division (truncates toward zero)."""
    q = abs(a) // abs(b)
    return -q if (a < 0) != (b < 0) else q


def encode_pub_key(pk: PubKey) -> bytes:
    """crypto.v1.PublicKey oneof: ed25519=1, secp256k1=2, bls12_381=3
    (48-byte min-pubkey-size compressed G1, matching CometBFT v1's
    keys.proto addition).

    sr25519 deliberately has no proto representation, matching the
    reference codec (crypto/encoding/codec.go:44-50; keys.proto:15-16)."""
    tag = pk.type_tag()
    if "Ed25519" in tag:
        return pb.f_bytes(1, pk.bytes(), emit_empty=True)
    if "Secp256k1" in tag:
        return pb.f_bytes(2, pk.bytes(), emit_empty=True)
    if "Bls12_381" in tag:
        return pb.f_bytes(3, pk.bytes(), emit_empty=True)
    raise ValueError(f"unsupported key type {tag}")


def decode_pub_key(fields: dict) -> PubKey:
    """Inverse of encode_pub_key from parsed proto fields {tag: bytes}."""
    from ..crypto.ed25519 import Ed25519PubKey
    from ..crypto.secp256k1 import Secp256k1PubKey

    if 1 in fields:
        return Ed25519PubKey(bytes(fields[1]))
    if 2 in fields:
        return Secp256k1PubKey(bytes(fields[2]))
    if 3 in fields:
        from ..crypto.bls import BlsPubKey

        return BlsPubKey(bytes(fields[3]))
    raise ValueError("unknown public key oneof")


def _simple_encode(pub_key: PubKey, voting_power: int) -> bytes:
    """SimpleValidator proto (pubkey + power), the hashing encoding."""
    return pb.f_embedded(1, encode_pub_key(pub_key)) + pb.f_varint(
        2, voting_power)


@dataclass
class Validator:
    address: bytes
    pub_key: PubKey
    voting_power: int
    proposer_priority: int = 0

    @classmethod
    def from_pub_key(cls, pk: PubKey, power: int) -> "Validator":
        return cls(pk.address(), pk, power)

    def copy(self) -> "Validator":
        return Validator(
            self.address, self.pub_key, self.voting_power, self.proposer_priority
        )


class Member(NamedTuple):
    """One row of a membership: who a validator is and what it weighs.
    Every set of one membership (a set and all its copy()s) shares the
    same Member objects; the proposer priority is the set's own
    (ValidatorSet.priorities()), so a Member has none."""

    address: bytes
    pub_key: PubKey
    voting_power: int


class KeyColumns(NamedTuple):
    """ValidatorSet.key_columns(): the set in validator order.

    addr_rows (n,20) u8, or None if some address is not 20 bytes;
    powers (n,) i64; curves maps each key type tag to (validator
    indices ascending, pubkey rows (k, width) u8, or None if the keys of
    that type differ in width)."""

    addr_rows: object
    powers: object
    curves: dict


def _sort_key(v):
    # voting power desc, then address asc
    return (-v.voting_power, v.address)


# --- the rotation (reference IncrementProposerPriority :116): scale the
# priorities into a window of 2 x total power, centre them on their
# average, then `times` elections: everyone gains its power, the highest
# priority (ties to the LOWER address) wins and pays the total power.
# Two implementations of the same arithmetic. rotate_integer is Python
# ints clipped to int64 at every step, as upstream's safeAddClip /
# safeSubClip: right for any input, the fallback, and the oracle of
# tests/test_validator_set.py. _rotate_column is int64 numpy over the
# priorities column, and ValidatorSet._rotate takes it only where no
# step can leave int64, so no clip can fire and the two agree.


def rotate_integer(prios: list[int], powers: list[int],
                   addresses: list[bytes], total: int,
                   times: int) -> tuple[list[int], int | None]:
    """(priorities after, index of the last winner or None at times=0)."""
    diff_max = PRIORITY_WINDOW_SIZE_FACTOR * total
    diff = max(prios) - min(prios)
    if 0 < diff_max < diff:
        ratio = (diff + diff_max - 1) // diff_max
        prios = [_trunc_div(p, ratio) for p in prios]
    # Go big.Int Euclidean Div (floor for positive divisor)
    avg = sum(prios) // len(prios)
    prios = [_clip(p - avg) for p in prios]
    winner = None
    for _ in range(times):
        prios = [_clip(p + w) for p, w in zip(prios, powers)]
        winner = 0
        for i in range(1, len(prios)):
            if prios[i] > prios[winner] or (
                    prios[i] == prios[winner]
                    and addresses[i] < addresses[winner]):
                winner = i
        prios[winner] = _clip(prios[winner] - total)
    return prios, winner


def _rotate_column(p, powers, address_rank, total: int, times: int,
                   spread: int):
    """rotate_integer on an int64 column `p` (not written to), for a
    caller that has shown nothing here can overflow. `spread` is max(p) -
    min(p) as a Python int; address_rank() gives each member's place in
    address order, asked for only at a tie."""
    diff_max = PRIORITY_WINDOW_SIZE_FACTOR * total
    if 0 < diff_max < spread:
        ratio = (spread + diff_max - 1) // diff_max
        q, r = np.divmod(p, ratio)
        q += (r != 0) & (p < 0)  # floor -> toward zero, as _trunc_div
        p = q
    p = p - (int(p.sum()) // len(p))
    winner = None
    for _ in range(times):
        p += powers
        top = np.flatnonzero(p == p.max())
        winner = int(top[0] if len(top) == 1
                     else top[address_rank()[top].argmin()])
        p[winner] -= total
    return p, winner


class ValidatorSet:
    """Ordered validator set with proposer rotation.

    A set is a membership and a column: `members`, the ordered (power
    desc, address asc) Member rows, shared with every copy() together
    with all that is derived from them (hash, total power, address
    index, key_columns()); and the set's own proposer priorities, one
    int64 column in member order. Rotating a copy costs a few vector
    operations and builds no per-member object; update_with_change_set
    is the one place that makes a new membership."""

    def __init__(self, validators: list[Validator], increment_first: bool = True,
                 proposer_address: bytes = b""):
        """`proposer_address` restores the proposer a stored set was
        saved with (the last winner, who is NOT the highest priority
        after paying for its turn)."""
        if not validators:
            raise ValueError("validator set must not be empty")
        self._frozen = False
        self._proposer: Member | None = None
        # encode()'s bytes, kept once the set is frozen
        self._enc: bytes | None = None
        self._set_membership(sorted(validators, key=_sort_key))
        if len(self._address_index()) != len(self.members):
            raise ValueError("duplicate validator address")
        self.total_voting_power()  # validates the cap
        if increment_first:
            self.increment_proposer_priority(1)
        if proposer_address:
            i = self._address_index().get(proposer_address)
            self._proposer = None if i is None else self.members[i]

    def _set_membership(self, vals: list[Validator]):
        """This set becomes `vals` (in order): a membership, with a memo,
        of its own, and their priorities as its column."""
        self.members: tuple[Member, ...] = tuple(
            Member(v.address, v.pub_key, v.voting_power) for v in vals)
        try:
            self._prio = np.array([v.proposer_priority for v in vals], np.int64)
        except OverflowError:
            raise ValueError("proposer priority outside int64") from None
        self._view: list[Validator] | None = None
        # what is derived from the membership alone, shared with every
        # copy(): state hands each height a fresh copy (proposer
        # rotation), made before anyone has asked the source for its
        # columns, so a memo kept per object would be rebuilt per block
        self._memo: dict = {}

    # --- queries ---

    def __len__(self) -> int:
        return len(self.members)

    @property
    def validators(self) -> list[Validator]:
        """The set as Validator objects carrying this set's priorities:
        a snapshot for readers that want rows (codecs, RPC, tests),
        built on first use and dropped when the priorities move. Writing
        to it does not change the set. Code on a block's path reads
        `members` and `priorities()` and never asks for it."""
        if self._view is None:
            self._view = [
                Validator(m.address, m.pub_key, m.voting_power, p)
                for m, p in zip(self.members, self._prio.tolist())
            ]
        return self._view

    def priorities(self) -> list[int]:
        """Proposer priorities in member order."""
        return self._prio.tolist()

    def _validator(self, i: int) -> Validator:
        if self._view is not None:
            return self._view[i]
        m = self.members[i]
        return Validator(m.address, m.pub_key, m.voting_power,
                         self._prio.item(i))

    def total_voting_power(self) -> int:
        total = self._memo.get("total_power")
        if total is None:
            total = 0
            for m in self.members:
                total += m.voting_power
                if total > MAX_TOTAL_VOTING_POWER:
                    raise ValueError("total voting power exceeds cap")
            self._memo["total_power"] = total
        return total

    def _address_index(self) -> dict[bytes, int]:
        # O(1) address index (10k-validator light-trusting verification
        # does one lookup per signature; a linear scan would be O(N^2)).
        index = self._memo.get("address_index")
        if index is None:
            index = self._memo["address_index"] = {
                m.address: i for i, m in enumerate(self.members)
            }
        return index

    def get_by_address(self, addr: bytes) -> tuple[int, Validator | None]:
        i = self._address_index().get(addr, -1)
        return (i, self._validator(i)) if i >= 0 else (-1, None)

    def get_by_index(self, idx: int) -> Validator | None:
        if 0 <= idx < len(self.members):
            return self._validator(idx)
        return None

    def has_address(self, addr: bytes) -> bool:
        return addr in self._address_index()

    def hash(self) -> bytes:
        # memoized: the hash covers only (pubkey, power) — membership
        # changes go through update_with_changeset (a new memo);
        # proposer-priority churn doesn't affect it. Replay hashes the
        # same set once per block otherwise (~ms each at 100 vals).
        h = self._memo.get("hash")
        if h is None:
            h = self._memo["hash"] = merkle.hash_from_byte_slices(
                [_simple_encode(m.pub_key, m.voting_power)
                 for m in self.members]
            )
        return h

    def key_columns(self) -> KeyColumns:
        """The set as numpy columns, for whole-commit verification
        without a trip through each Validator. Memoized per membership:
        the same set judges thousands of consecutive commits."""
        cols = self._memo.get("key_cols")
        if cols is not None:
            return cols
        n = len(self.members)
        by_tag: dict[str, tuple[list[int], list[bytes]]] = {}
        for i, v in enumerate(self.members):
            idxs, pubs = by_tag.setdefault(v.pub_key.type_tag(), ([], []))
            idxs.append(i)
            pubs.append(v.pub_key.bytes())
        curves = {}
        for tag, (idxs, pubs) in by_tag.items():
            width = len(pubs[0])
            rows = None
            if width and all(len(p) == width for p in pubs):
                rows = np.frombuffer(b"".join(pubs), np.uint8).reshape(
                    len(pubs), width)
            curves[tag] = (np.asarray(idxs, np.int64), rows)
        addrs = [v.address for v in self.members]
        cols = KeyColumns(
            np.frombuffer(b"".join(addrs), np.uint8).reshape(n, 20)
            if all(len(a) == 20 for a in addrs) else None,
            np.asarray([v.voting_power for v in self.members], np.int64),
            curves,
        )
        self._memo["key_cols"] = cols
        return cols

    def ed25519_columns(self):
        """(addr_rows (n,20) u8, pub_rows (n,32) u8, powers i64): the
        view of key_columns() an all-ed25519 set gives, or None when any
        key is of another type."""
        cols = self.key_columns()
        if list(cols.curves) != ["tendermint/PubKeyEd25519"]:
            return None
        _, pub_rows = cols.curves["tendermint/PubKeyEd25519"]
        if pub_rows is None or cols.addr_rows is None:
            return None
        return cols.addr_rows, pub_rows, cols.powers

    def all_bls(self) -> bool:
        """True when every validator key is BLS12-381 — the gate for
        certificate-native folding. Memoized like ed25519_columns:
        consensus consults it once per commit on a frozen set."""
        bls = self._memo.get("all_bls")
        if bls is None:
            bls = self._memo["all_bls"] = all(
                m.pub_key.type_tag() == "tendermint/PubKeyBls12_381"
                for m in self.members
            )
        return bls

    def freeze(self) -> "ValidatorSet":
        """Seal the set against mutation. State snapshots share (alias)
        ValidatorSet objects instead of defensively copying; the safety
        convention is that every mutator operates on a private .copy()
        first. freeze() makes a convention violation fail loudly instead
        of silently corrupting historical sets."""
        self._frozen = True
        return self

    def _assert_mutable(self):
        if self._frozen:
            raise RuntimeError(
                "mutating a frozen ValidatorSet (aliased by a State "
                "snapshot) — call .copy() first"
            )

    def copy(self) -> "ValidatorSet":
        """A set of the same membership (shared, not copied) with its
        own priorities, mutable whether or not this one is frozen."""
        vs = ValidatorSet.__new__(ValidatorSet)
        vs.members = self.members
        vs._memo = self._memo
        vs._prio = self._prio.copy()
        vs._proposer = self._proposer
        vs._view = None
        vs._frozen = False
        vs._enc = None
        return vs

    def encode(self) -> bytes:
        """The stored ValidatorSet proto: a Validator (address, public
        key, power, proposer priority) a member under field 1, then the
        proposer's address. A frozen set cannot change, so it keeps its
        bytes (its own: the priorities are not the membership's); a
        mutable one is encoded afresh every time and keeps nothing.
        What never changes in a member's record (all but the priority)
        is built once a membership."""
        enc = self._enc
        state_metrics().valset_encode_total.inc(
            1.0, "miss" if enc is None else "hit")
        if enc is not None:
            return enc
        consts = self._memo.get("enc_consts")
        if consts is None:
            consts = self._memo["enc_consts"] = [
                pb.f_bytes(1, m.address)
                + pb.f_embedded(2, encode_pub_key(m.pub_key))
                + pb.f_varint(3, m.voting_power)
                for m in self.members
            ]
        parts = []
        for const, priority in zip(consts, self._prio.tolist()):
            record = (const + _TAG_PRIORITY + pb.varint_i64(priority)
                      if priority else const)
            parts += (_TAG_VALIDATOR, pb.uvarint(len(record)), record)
        parts.append(pb.f_bytes(2, self.get_proposer().address))
        enc = b"".join(parts)
        if self._frozen:
            self._enc = enc
        return enc

    # --- proposer priority machinery ---

    def _address_rank(self):
        """Each member's place in address order, (n,) i64: what breaks a
        tie for the highest priority."""
        rank = self._memo.get("address_rank")
        if rank is None:
            addresses = [m.address for m in self.members]
            order = sorted(range(len(addresses)), key=addresses.__getitem__)
            rank = np.empty(len(order), np.int64)
            rank[order] = np.arange(len(order))
            self._memo["address_rank"] = rank
        return rank

    def _rotate(self, times: int) -> str:
        """Rescale, centre, `times` elections (0 after a change of the
        membership, which elects nobody); says which arithmetic ran."""
        self._assert_mutable()
        total = self.total_voting_power()
        p = self._prio
        lo, hi = p.min().item(), p.max().item()
        reach = max(-lo, hi)
        terms = self._memo.get("rotation")
        if terms is None:
            # the powers column (none where a power leaves int64: the
            # guard below then fails for any election), and the most one
            # election moves a priority by: its power up, the total down
            powers = [m.voting_power for m in self.members]
            step = abs(total) + max(map(abs, powers))
            terms = self._memo["rotation"] = (
                np.asarray(powers, np.int64) if step < 1 << 63 else None,
                step)
        powers, step = terms
        # int64 holds every step: the sum of n priorities; a priority
        # less the average (2 x reach at most), then `times` elections
        if (len(p) * reach < 1 << 62
                and 2 * reach + times * step < 1 << 63):
            path = "column"
            self._prio, winner = _rotate_column(
                p, powers, self._address_rank, total, times, hi - lo)
        else:
            path = "integer"
            prios, winner = rotate_integer(
                p.tolist(), [m.voting_power for m in self.members],
                [m.address for m in self.members], total, times)
            self._prio = np.array(prios, np.int64)
        self._view = None
        if winner is not None:
            self._proposer = self.members[winner]
        return path

    def increment_proposer_priority(self, times: int) -> str:
        """Rotates the proposer `times` turns on; returns which arithmetic
        ran, `column` (int64 numpy) or `integer` (Python ints, where the
        set's magnitudes could leave int64): same answers, the label is
        for the counters."""
        if times <= 0:
            raise ValueError("times must be positive")
        return self._rotate(times)

    def get_proposer(self) -> Member:
        """The winner of the last rotation; on a set never rotated, the
        highest priority (ties to the lower address)."""
        if self._proposer is None:
            prios = self._prio.tolist()
            top = max(prios)
            self._proposer = min(
                (m for m, p in zip(self.members, prios) if p == top),
                key=lambda m: m.address)
        return self._proposer

    def copy_increment_proposer_priority(self, times: int) -> "ValidatorSet":
        vs = self.copy()
        vs.increment_proposer_priority(times)
        return vs

    # --- updates (ABCI validator changes) ---

    def update_with_change_set(self, changes: list[Validator]):
        """Apply power updates / removals (power 0), reference :594-643.

        New validators enter with priority -(P' + P'>>3) where P' is
        tvpAfterUpdatesBeforeRemovals — the total power with all updates
        applied but removals NOT yet applied (reference verifyUpdates
        :423-455, computeNewPriorities :479); priorities are then rescaled
        into the window and recentered, in that order (:638-639).
        """
        self._assert_mutable()
        if not changes:
            return
        by_addr = {}
        for c in changes:
            if c.address in by_addr:
                raise ValueError("duplicate address in change set")
            if c.voting_power < 0:
                raise ValueError("negative voting power")
            by_addr[c.address] = c

        removals = {a for a, c in by_addr.items() if c.voting_power == 0}
        for a in removals:
            if not self.has_address(a):
                raise ValueError("removing non-existent validator")

        # tvp after updates, before removals (reference verifyUpdates):
        # old total plus the delta of every non-removal change.
        tvp_updates = self.total_voting_power()
        for a, c in by_addr.items():
            if c.voting_power == 0:
                continue
            _, old = self.get_by_address(a)
            tvp_updates += c.voting_power - (old.voting_power if old else 0)
        removed_power = sum(
            self.get_by_address(a)[1].voting_power for a in removals
        )
        if tvp_updates - removed_power > MAX_TOTAL_VOTING_POWER:
            raise ValueError("total voting power exceeds cap after update")

        kept = [v for v in self.validators if v.address not in removals]
        updated = []
        new_addrs = []
        for v in kept:
            c = by_addr.get(v.address)
            if c is not None and c.voting_power != 0:
                nv = v.copy()
                nv.voting_power = c.voting_power
                nv.pub_key = c.pub_key
                updated.append(nv)
            else:
                updated.append(v.copy())
        existing = {v.address for v in updated}
        for a, c in by_addr.items():
            if c.voting_power > 0 and a not in existing:
                nv = c.copy()
                updated.append(nv)
                new_addrs.append(a)

        if not updated:
            raise ValueError("applying changes would empty the validator set")

        penalty = -(tvp_updates + (tvp_updates >> 3))
        new_set = set(new_addrs)
        for v in updated:
            if v.address in new_set:
                v.proposer_priority = penalty

        # (the proposer stays the last rotation's winner, as upstream's
        # field does, even one that has just left: the next rotation,
        # which every caller makes, elects from the new membership)
        self._set_membership(sorted(updated, key=_sort_key))
        self.total_voting_power()
        # scale into the priority window, then center (reference order)
        self._rotate(0)
