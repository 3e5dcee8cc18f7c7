"""Deterministic fixtures: signers, validator sets, commits, chains.

Mirrors the role of the reference's internal/test fixture kit (commit.go,
validator.go): every layer's tests build real, verifiable artifacts. For
large validator sets the Ed25519 keys are *scalar signers* — the secret is
a raw scalar a with pubkey [a]B computed by the device fixed-base ladder in
one batch, and signatures finished host-side as S = r + k*a (mod L). These
are standard verifiable Ed25519 signatures; only derivation-from-seed is
skipped, which verifiers never see.
"""

from __future__ import annotations

import functools
import hashlib
from dataclasses import dataclass

import numpy as np

from ..crypto import ed25519_ref as ref
from ..crypto.ed25519 import Ed25519PubKey
from ..encoding import proto as pb
from ..types import (
    Block,
    BlockID,
    Commit,
    CommitSig,
    Data,
    Header,
    PartSetHeader,
    Timestamp,
    Validator,
    ValidatorSet,
)
from ..types.block import BlockIDFlag


@dataclass
class ScalarSigner:
    scalar: int
    pub_bytes: bytes

    def pub_key(self) -> Ed25519PubKey:
        return Ed25519PubKey(self.pub_bytes)

    def address(self) -> bytes:
        return self.pub_key().address()


@functools.lru_cache(maxsize=8)
def _fixed_base_fn(n: int):
    import jax
    import jax.numpy as jnp

    from ..ops import curve as C

    @jax.jit
    def run(digs):
        return C.compress(C.fixed_base(digs))

    return run


def _fixed_base_batch(scalars: list[int]) -> np.ndarray:
    """[s]B for a batch of scalars via the device ladder -> (N, 32) encodings.

    Padded to power-of-two buckets so each bucket size compiles once.
    """
    import jax.numpy as jnp

    from ..crypto.ed25519 import _bucket
    from ..ops import curve as C

    n = len(scalars)
    b = _bucket(max(n, 1))
    padded = scalars + [1] * (b - n)
    digs = jnp.asarray(C.scalar_digits(padded))
    return np.asarray(_fixed_base_fn(b)(digs))[:n]


def make_signers(n: int, seed: int = 0) -> list[ScalarSigner]:
    rng = np.random.default_rng(seed)
    scalars = [int.from_bytes(rng.bytes(32), "little") % ref.L or 1 for _ in range(n)]
    pubs = _fixed_base_batch(scalars)
    return [ScalarSigner(s, pubs[i].tobytes()) for i, s in enumerate(scalars)]


class RPool:
    """Pre-batched R nonce points for chunked chain generation.

    batch_sign's per-call _fixed_base_batch pays one device round trip
    per commit (its cost is unmeasured on today's machine; at ~150 ms,
    as once measured, a 50k-block x 1000-signer chain spends 2+ hours
    on round trips alone). The pool
    computes R encodings for `blocks_per_fill` commits in ONE device
    call and hands them out per block."""

    def __init__(self, n_signers: int, blocks_per_fill: int = 32,
                 seed: int = 1):
        self.n = n_signers
        self.per_fill = blocks_per_fill
        self.seed = seed
        self._buf: list[tuple[list[int], np.ndarray]] = []

    def next(self) -> tuple[list[int], np.ndarray]:
        if not self._buf:
            rng = np.random.default_rng(self.seed)
            self.seed += 1
            total = self.n * self.per_fill
            rs = [
                int.from_bytes(rng.bytes(32), "little") % ref.L or 1
                for _ in range(total)
            ]
            encs = _fixed_base_batch(rs)
            for i in range(self.per_fill):
                lo = i * self.n
                self._buf.append((rs[lo:lo + self.n], encs[lo:lo + self.n]))
        return self._buf.pop()


def batch_sign(signers: list[ScalarSigner], msgs: list[bytes], seed: int = 1,
               nonces: tuple[list[int], np.ndarray] | None = None) -> list[bytes]:
    """One signature per (signer, msg) pair, R points computed on device
    (or taken from a pre-batched RPool draw via `nonces`)."""
    if nonces is not None:
        rs, r_encs = nonces
        rs, r_encs = rs[:len(signers)], r_encs[:len(signers)]
    else:
        rng = np.random.default_rng(seed)
        rs = [int.from_bytes(rng.bytes(32), "little") % ref.L or 1 for _ in signers]
        r_encs = _fixed_base_batch(rs)
    sigs = []
    for signer, msg, r, r_enc in zip(signers, msgs, rs, r_encs):
        r_b = r_enc.tobytes()
        k = int.from_bytes(
            hashlib.sha512(r_b + signer.pub_bytes + msg).digest(), "little"
        ) % ref.L
        s = (r + k * signer.scalar) % ref.L
        sigs.append(r_b + s.to_bytes(32, "little"))
    return sigs


def sign_with_scalar(signer: ScalarSigner, msg: bytes) -> bytes:
    """One host-side signature (deterministic nonce); for single-vote paths
    (consensus state machine, privval) where device batching has nothing to
    amortize. Standard verifiable Ed25519 output."""
    r = (
        int.from_bytes(
            hashlib.sha512(b"nonce" + signer.pub_bytes + msg).digest(), "little"
        )
        % ref.L
        or 1
    )
    r_enc = ref._encode_point(*ref._ext_to_affine(ref._ext_scalar_mul(r, ref.B_POINT)))
    k = (
        int.from_bytes(
            hashlib.sha512(r_enc + signer.pub_bytes + msg).digest(), "little"
        )
        % ref.L
    )
    s = (r + k * signer.scalar) % ref.L
    return r_enc + s.to_bytes(32, "little")


def sign_vote(signer: ScalarSigner, vote, chain_id: str) -> None:
    vote.signature = sign_with_scalar(signer, vote.sign_bytes(chain_id))


def make_validator_set(
    signers: list[ScalarSigner], powers: list[int] | None = None
) -> ValidatorSet:
    powers = powers or [10] * len(signers)
    return ValidatorSet(
        [Validator.from_pub_key(s.pub_key(), p) for s, p in zip(signers, powers)]
    )


def make_block_id(tag: bytes = b"block") -> BlockID:
    h = hashlib.sha256(tag).digest()
    return BlockID(h, PartSetHeader(1, hashlib.sha256(tag + b"parts").digest()))


from ..types.block import block_id_for  # re-export for existing callers


def make_chain(
    n_blocks: int,
    n_validators: int = 4,
    chain_id: str = "replay-chain",
    txs_per_block: int = 2,
    app=None,
    block_store=None,
    seed: int = 0,
    backend: str = "cpu",
    nil_votes: dict[int, set[int]] | None = None,
    corrupt_sig: tuple[int, int] | None = None,
    verify_last_commit: bool = True,
    r_pool: "RPool | None" = None,
    start_state=None,
    start_commit: Commit | None = None,
    start_height: int = 1,
    powers: list[int] | None = None,
    extra_txs=None,
    spare_signers: list[ScalarSigner] | None = None,
    stale_set_at: int | None = None,
):
    """Generate a fully-valid signed chain by actually running the executor.

    Returns (block_store, final_state, genesis_state, signers). Every block
    is built with create_proposal_block, committed by all validators
    (device-batched signing), and applied through ABCI — so replaying the
    store reproduces byte-identical state.

    nil_votes maps height -> validator indices casting NIL precommits in
    that height's commit. corrupt_sig=(height, idx) flips a byte of that
    commit signature after signing (the corrupted commit still propagates
    into the next block's embedded LastCommit, so verification during
    generation is elided for such chains — they exist to test that replay
    REJECTS them).

    verify_last_commit=False skips LastCommit verification during
    generation: the commits are signed here and known-valid, and at
    north-star scale (50k blocks x 1000 validators) re-verifying each
    one with the pure-Python oracle costs ~4.4 s/block — the REPLAY of
    the generated store is where verification is measured. r_pool
    amortizes the device nonce-point round trip over many blocks.
    start_state/start_commit/start_height continue a chain from a prior
    make_chain call's (state, last_commit) so arbitrarily long chains
    build in bounded-memory chunks into one shared block_store.

    A chain whose validator set changes: `powers` are the genesis powers
    (default 10 each); extra_txs(height, state) returns the transactions
    block `height` carries behind its own, `state` being the one the block
    is proposed on (the kvstore app turns a val_tx() into a validator
    update, in force two heights on; ValsetChurn draws them from a seed).
    txs_per_block=0 with extra_txs gives blocks that carry what extra_txs
    returns and nothing else: LoadtimeTxs makes the transactions of
    upstream's QA load that way (400 of 1,024 bytes a block).
    spare_signers hold the keys of members that join later. Every commit
    is signed by the set the state gives for its height, except at
    stale_set_at, where the set of the height BEFORE signs (as
    corrupt_sig, a chain that replay must refuse).
    """
    from ..abci.client import AppConns
    from ..abci.kvstore import KVStoreApp
    from ..state.execution import BlockExecutor, make_genesis_state
    from ..storage import BlockStore, MemKV

    signers = make_signers(n_validators, seed=seed)
    vals = make_validator_set(signers, powers)
    by_addr = {s.address(): s for s in signers + (spare_signers or [])}
    app = app or KVStoreApp()
    store = block_store or BlockStore(MemKV())
    executor = BlockExecutor(AppConns(app), backend=backend)
    genesis = make_genesis_state(chain_id, vals)
    state = start_state if start_state is not None else genesis.copy()

    last_commit = start_commit if start_commit is not None else Commit()
    for h in range(start_height, start_height + n_blocks):
        txs = [b"k%d-%d=v%d" % (h, i, i) for i in range(txs_per_block)]
        if extra_txs is not None:
            txs += extra_txs(h, state)
        proposer = state.validators.get_proposer()
        block = executor.create_proposal_block(
            h, state, last_commit, proposer.address, txs,
            block_time=state.last_block_time,
        )
        bid = block_id_for(block)
        vals_h = state.validators  # the set that signs height h's commit
        if stale_set_at == h:
            vals_h = state.last_validators
        state = executor.apply_block(
            state, bid, block,
            last_commit_preverified=(
                corrupt_sig is not None or stale_set_at is not None
                or not verify_last_commit
            ),
        )
        commit = make_commit(
            chain_id, h, 0, bid, vals_h, by_addr,
            time_ns=state.last_block_time.unix_ns() + 1_000_000_000,
            nil=(nil_votes or {}).get(h),
            r_pool=r_pool,
        )
        if corrupt_sig is not None and corrupt_sig[0] == h:
            cs = commit.signatures[corrupt_sig[1]]
            sig = bytearray(cs.signature)
            sig[0] ^= 0xFF
            cs.signature = bytes(sig)
            commit.invalidate_memos()
        store.save_block(block, commit)
        last_commit = commit
    return store, state, genesis, signers


def val_tx(pub_bytes: bytes, power: int) -> bytes:
    """The kvstore app's validator-update transaction (upstream
    abci/example/kvstore): power 0 removes the member."""
    return b"val:%s=%d" % (pub_bytes.hex().encode(), power)


class ValsetChurn:
    """make_chain's extra_txs on the schedule of upstream's e2e manifests
    (test/e2e/networks/ci.toml: `[validator_update.<height>]` tables ten
    heights apart, first one that adds a validator, then one that restates
    its members' powers), drawn from `seed`: every `every` heights a block
    carries validator updates, by turns a join (a key of `spare`, never
    seen before, at a drawn power, while the member lowest in the order
    leaves with power 0, so the set keeps its size) and a re-powering
    (`repowered` members take a drawn power). Powers are drawn as the e2e
    generator draws them, uniformly from power_lo..power_hi. The updates of
    block H change the set of H+2 (state.next_validators is the set they
    are applied to)."""

    def __init__(self, spare: list[ScalarSigner], *, seed: int, every: int,
                 repowered: int, power_lo: int, power_hi: int):
        self.spare = list(spare)
        self.rng = np.random.default_rng([seed, 36])
        self.every, self.repowered = every, repowered
        self.power_lo, self.power_hi = power_lo, power_hi
        self.joins = self.repowerings = 0

    def power(self) -> int:
        return int(self.rng.integers(self.power_lo, self.power_hi + 1))

    def genesis_powers(self, n: int) -> list[int]:
        return [self.power() for _ in range(n)]

    def __call__(self, height: int, state) -> list[bytes]:
        turn, rest = divmod(height, self.every)
        if rest:
            return []
        members = state.next_validators.validators
        if turn % 2 and self.spare:
            self.joins += 1
            return [val_tx(members[-1].pub_key.bytes(), 0),
                    val_tx(self.spare.pop().pub_bytes, self.power())]
        self.repowerings += 1
        picked = self.rng.choice(len(members), size=self.repowered,
                                 replace=False)
        return [val_tx(members[int(i)].pub_key.bytes(), self.power())
                for i in picked]


class LoadtimeTxs:
    """make_chain's extra_txs for blocks that carry upstream's QA load
    (docs/qa: the testnets are loaded by test/loadtime with transactions of
    `size` bytes through `connections` connections at `rate` tx/s each).
    A transaction is what test/loadtime/payload NewBytes makes: the key
    prefix `a=` and the hex of a protobuf Payload (connections = 1, rate =
    2, size = 3, time = 4 as a google.protobuf.Timestamp, id = 5, padding =
    6), the padding sized so that the whole transaction is `size` bytes
    (hex doubles the payload, so `size` is even; a few small sizes that no
    padding reaches are refused). The kvstore app takes it as the key `a`:
    the store holds one entry however many arrive.

    Drawn from `seed`: a 16-byte id a block (loadtime draws one a run of
    the tool) and the padding's bytes. Transaction i of block `height`
    carries the time at which a generator of `rate` tx/s sends it in the
    height's own second: `height` s + i / rate s past the genesis time.
    txs(height) is a function of (seed, height) alone."""

    KEY_PREFIX = b"a="
    GENESIS_S = 1_700_000_000

    def __init__(self, seed: int, per_block: int = 400, size: int = 1024,
                 connections: int = 1, rate: int = 400):
        if size % 2:
            raise ValueError("a loadtime transaction is hex: size is even")
        self.seed, self.per_block, self.size = seed, per_block, size
        self.connections, self.rate = connections, rate

    def tx(self, height: int, i: int, run_id: bytes, rng) -> bytes:
        nanos = (i % self.rate) * 1_000_000_000 // self.rate
        when = Timestamp(self.GENESIS_S + height + i // self.rate, nanos)
        head = (pb.f_varint(1, self.connections) + pb.f_varint(2, self.rate)
                + pb.f_varint(3, self.size) + pb.f_embedded(4, when.encode())
                + pb.f_bytes(5, run_id))
        # what is left of (size - 2) / 2 payload bytes behind the padding's
        # tag holds the padding and its length's varint
        room = (self.size - len(self.KEY_PREFIX)) // 2 - len(head) - 1
        n = next((n for n in (room - 1, room - 2, room - 3)
                  if n >= 1 and n + len(pb.uvarint(n)) == room), None)
        if n is None:
            raise ValueError(f"no padding makes this transaction {self.size} "
                             f"bytes")
        payload = head + pb.f_bytes(6, rng.bytes(n))
        return self.KEY_PREFIX + payload.hex().encode()

    def txs(self, height: int) -> list[bytes]:
        rng = np.random.default_rng([self.seed, 40, height])
        run_id = rng.bytes(16)
        return [self.tx(height, i, run_id, rng)
                for i in range(self.per_block)]

    def __call__(self, height: int, state) -> list[bytes]:
        return self.txs(height)

    @classmethod
    def parse(cls, tx: bytes) -> dict:
        """A transaction back to its payload's fields (what
        test/loadtime/payload FromBytes reads)."""
        if not tx.startswith(cls.KEY_PREFIX):
            raise ValueError("not a loadtime transaction")
        d = pb.fields_to_dict(bytes.fromhex(tx[len(cls.KEY_PREFIX):].decode()))
        return {"connections": int(d.get(1, 0)), "rate": int(d.get(2, 0)),
                "size": int(d.get(3, 0)),
                "time": Timestamp.decode(pb.as_bytes(d.get(4, b""))),
                "id": pb.as_bytes(d.get(5, b"")),
                "padding": pb.as_bytes(d.get(6, b""))}


def make_commit(
    chain_id: str,
    height: int,
    round_: int,
    block_id: BlockID,
    vals: ValidatorSet,
    signers_by_addr: dict[bytes, ScalarSigner],
    time_ns: int = 1_700_000_000_000_000_000,
    absent: set[int] | None = None,
    nil: set[int] | None = None,
    sign_seed: int | None = None,
    r_pool: "RPool | None" = None,
) -> Commit:
    """A commit signed by every validator (minus `absent` indices; `nil`
    indices sign a NIL precommit), ordered to match the validator set."""
    absent = absent or set()
    nil = nil or set()
    commit = Commit(height=height, round=round_, block_id=block_id, signatures=[])
    sig_slots = []
    signers, msgs = [], []
    for idx, val in enumerate(vals.members):
        if idx in absent:
            commit.signatures.append(CommitSig.absent())
            sig_slots.append(None)
            continue
        ts = Timestamp.from_unix_ns(time_ns + idx)
        cs = CommitSig(
            block_id_flag=BlockIDFlag.NIL if idx in nil else BlockIDFlag.COMMIT,
            validator_address=val.address,
            timestamp=ts,
            signature=b"",
        )
        commit.signatures.append(cs)
        sig_slots.append(idx)
        signers.append(signers_by_addr[val.address])
        msgs.append(None)  # filled after sign bytes known
    # sign bytes depend on the commit structure built above
    j = 0
    for idx in range(len(vals)):
        if sig_slots[idx] is None:
            continue
        msgs[j] = commit.vote_sign_bytes(chain_id, idx)
        j += 1
    sigs = batch_sign(
        signers, msgs, seed=(sign_seed if sign_seed is not None else height),
        nonces=r_pool.next() if r_pool is not None else None,
    )
    j = 0
    for idx in range(len(vals)):
        if sig_slots[idx] is None:
            continue
        commit.signatures[idx].signature = sigs[j]
        j += 1
    return commit
