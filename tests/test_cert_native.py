"""Certificate-native consensus (ISSUE 17): CertCommit codec +
one-decode-path migration, fold fallbacks, verdict pins vs the
signature column, the blockstore evidence window, WAL framing,
an in-process all-BLS net committing cert-native end to end with the
cert-gossip outcome classification, light verification over cert headers,
replication feed frames, and cert-path replay accept/reject.
"""

from __future__ import annotations

import pytest

from cometbft_tpu.abci.client import AppConns
from cometbft_tpu.abci.kvstore import KVStoreApp
from cometbft_tpu.crypto import bls
from cometbft_tpu.state.execution import BlockExecutor, make_genesis_state
from cometbft_tpu.storage import BlockStore, MemKV, StateStore
from cometbft_tpu.types import (
    BlockID,
    BlockIDFlag,
    Commit,
    CommitSig,
    PartSetHeader,
    Timestamp,
)
from cometbft_tpu.types.agg_commit import (
    AggCommitError,
    AggregateCommit,
    CertCommit,
    decode_commit_any,
    fold_commit,
)
from cometbft_tpu.types.block import block_id_for
from cometbft_tpu.types.validation import (
    ErrInvalidSignature,
    ErrNotEnoughVotingPower,
    verify_cert_trusting,
    verify_commit,
    verify_commit_light,
)
from cometbft_tpu.types.validator_set import Validator, ValidatorSet
from cometbft_tpu.types.vote import SignedMsgType, canonical_vote_bytes

CHAIN = "cert-chain"
BID = BlockID(b"\xaa" * 32, PartSetHeader(1, b"\xbb" * 32))
TS = Timestamp(1_700_000_000, 0)


@pytest.fixture(scope="module")
def keyring():
    return [bls.BlsPrivKey.from_secret(b"certnat-%d" % i) for i in range(4)]


@pytest.fixture(scope="module")
def valset(keyring):
    return ValidatorSet(
        [Validator.from_pub_key(k.pub_key(), 10) for k in keyring]
    )


def _column(keyring, valset, height=7, absent=(), corrupt=None,
            ts_skew=()):
    """Full-column precommit Commit in canonical validator order."""
    by_addr = {k.pub_key().address(): k for k in keyring}
    sigs = []
    for i, val in enumerate(valset.validators):
        if i in absent:
            sigs.append(CommitSig.absent())
            continue
        ts = Timestamp(TS.seconds + (1 if i in ts_skew else 0), TS.nanos)
        msg = canonical_vote_bytes(
            SignedMsgType.PRECOMMIT, height, 0, BID, ts, CHAIN)
        sig = by_addr[val.address].sign(msg)
        if i == corrupt:
            sig = bytes([sig[0] ^ 0xFF]) + sig[1:]
        sigs.append(CommitSig(BlockIDFlag.COMMIT, val.address, ts, sig))
    c = Commit(height=height, round=0, block_id=BID, signatures=sigs)
    c.invalidate_memos()
    return c


def _bls_chain(n_blocks, keyring, valset, cert_native=True):
    """Executor-built all-BLS chain with uniform precommit timestamps —
    the fold succeeds at every height when cert_native."""
    by_addr = {k.pub_key().address(): k for k in keyring}
    store = BlockStore(MemKV())
    executor = BlockExecutor(AppConns(KVStoreApp()))
    genesis = make_genesis_state(CHAIN, valset)
    state = genesis.copy()
    last_commit = Commit()
    for h in range(1, n_blocks + 1):
        proposer = state.validators.get_proposer()
        block = executor.create_proposal_block(
            h, state, last_commit, proposer.address, [b"k%d=v" % h],
            block_time=state.last_block_time,
        )
        bid = block_id_for(block)
        vals_h = state.validators
        state = executor.apply_block(
            state, bid, block, last_commit_preverified=True)
        ts = Timestamp.from_unix_ns(
            state.last_block_time.unix_ns() + 1_000_000_000)
        msg = canonical_vote_bytes(
            SignedMsgType.PRECOMMIT, h, 0, bid, ts, CHAIN)
        commit = Commit(height=h, round=0, block_id=bid, signatures=[
            CommitSig(BlockIDFlag.COMMIT, v.address, ts,
                      by_addr[v.address].sign(msg))
            for v in vals_h.validators
        ])
        commit.invalidate_memos()
        if cert_native:
            commit = fold_commit(commit, vals_h)
            assert isinstance(commit, CertCommit)
        store.save_block(block, commit)
        last_commit = commit
    return store, state, genesis


@pytest.fixture(scope="module")
def cert_chain(keyring, valset):
    return _bls_chain(6, keyring, valset, cert_native=True)


@pytest.fixture(scope="module")
def ed_chain():
    from cometbft_tpu.utils.factories import make_chain

    return make_chain(5, n_validators=4, chain_id="ed-chain",
                      backend="cpu")


# ---------------------------------------------------------------- codec ----
def test_certcommit_codec_roundtrip(keyring, valset):
    cc = CertCommit.from_commit(_column(keyring, valset))
    back = CertCommit.decode(cc.encode())
    assert back == cc
    assert back.hash() == cc.hash()
    assert back.height == 7 and back.size() == 4
    assert back.signer_count() == 4
    # tampered aggregate size / bitmap-size mismatch both refuse decode
    with pytest.raises((AggCommitError, ValueError)):
        CertCommit.decode(cc.encode()[:-4])
    bad_bitmap = CertCommit(
        AggregateCommit(cc.cert.height, cc.cert.round, cc.cert.block_id,
                        cc.cert.timestamp, b"\x0f\x00", cc.cert.agg_sig),
        cc.size_)
    with pytest.raises(AggCommitError):
        CertCommit.decode(bad_bitmap.encode())


def test_decode_commit_any_routes_both_formats(keyring, valset):
    col = _column(keyring, valset)
    cc = CertCommit.from_commit(col)
    assert isinstance(decode_commit_any(col.encode()), Commit)
    assert isinstance(decode_commit_any(cc.encode()), CertCommit)
    assert decode_commit_any(cc.encode()) == cc
    # genesis empty commit has no field >= 4 at all
    assert isinstance(decode_commit_any(Commit().encode()), Commit)


def test_decode_commit_any_matches_seed_decoder(keyring, valset):
    """Migration differential (ISSUE 17): pre-certificate stores hold
    plain signature columns; the one shared read path must parse them
    exactly as the seed's Commit.decode did — same commit, same hash."""
    for absent in ((), (1,), (0, 2)):
        buf = _column(keyring, valset, absent=absent).encode()
        a = Commit.decode(buf)
        b = decode_commit_any(buf)
        assert isinstance(b, Commit)
        assert a.encode() == b.encode()
        assert a.hash() == b.hash()


# ----------------------------------------------------------------- fold ----
def test_fold_commit_fallbacks(keyring, valset, ed_chain):
    # uniform all-BLS folds and the certificate verifies
    folded = fold_commit(_column(keyring, valset), valset)
    assert isinstance(folded, CertCommit)
    folded.verify(CHAIN, valset)
    # non-uniform timestamps: silently unchanged
    skew = _column(keyring, valset, ts_skew=(2,))
    assert fold_commit(skew, valset) is skew
    # ed25519 set: silently unchanged (the byte-identity guarantee)
    estore, estate, _g, _s = ed_chain
    ecommit = estore.load_seen_commit(2)
    assert fold_commit(ecommit, estate.validators) is ecommit
    # empty commit: unchanged
    empty = Commit()
    assert fold_commit(empty, valset) is empty


def test_mixed_valset_falls_back_to_columns(keyring):
    """Satellite back-compat: a BLS+ed25519 valset never folds — the
    column survives fold_commit untouched, round-trips the shared read
    seam byte-identically, and verifies through the per-sig path."""
    from cometbft_tpu.crypto.ed25519 import Ed25519PrivKey

    mixed = keyring[:2] + [
        Ed25519PrivKey(bytes([40 + i]) * 32) for i in range(2)
    ]
    vals = ValidatorSet(
        [Validator.from_pub_key(k.pub_key(), 10) for k in mixed]
    )
    assert not vals.all_bls()
    col = _column(mixed, vals)
    wire = col.encode()
    assert fold_commit(col, vals) is col
    assert col.encode() == wire
    back = decode_commit_any(wire)
    assert isinstance(back, Commit)
    assert back.encode() == wire
    verify_commit(CHAIN, vals, BID, 7, col)
    verify_commit_light(CHAIN, vals, BID, 7, col)


# -------------------------------------------------------- verdict pins ----
def test_cert_and_column_verdicts_agree(keyring, valset):
    """The certificate path must accept and reject exactly where the
    signature column does — same exception classes on both sides."""
    def verdict(commit):
        try:
            verify_commit(CHAIN, valset, BID, 7, commit)
            return "accept"
        except Exception as e:  # noqa: BLE001 — the class IS the verdict
            return type(e).__name__

    full = _column(keyring, valset)
    short = _column(keyring, valset, absent=(2, 3))  # 20 <= 26 threshold
    bad_col = _column(keyring, valset, corrupt=1)
    folded = CertCommit.from_commit(full)
    c = folded.cert
    bad_cert = CertCommit(
        AggregateCommit(c.height, c.round, c.block_id, c.timestamp,
                        c.bitmap,
                        bytes([c.agg_sig[0] ^ 0xFF]) + c.agg_sig[1:]),
        folded.size_)
    assert verdict(full) == verdict(folded) == "accept"
    assert (verdict(short) == verdict(CertCommit.from_commit(short))
            == "ErrNotEnoughVotingPower")
    assert verdict(bad_col) == verdict(bad_cert) == "ErrInvalidSignature"
    # the light variant takes the same cert branch
    verify_commit_light(CHAIN, valset, BID, 7, folded)
    with pytest.raises(ErrInvalidSignature):
        verify_commit_light(CHAIN, valset, BID, 7, bad_cert)


def test_verify_cert_trusting(keyring, valset):
    folded = CertCommit.from_commit(_column(keyring, valset))
    verify_cert_trusting(CHAIN, valset, valset, folded)
    # bitmap signers hold only 2/4 of the trusted power: 20 <= 26
    two = CertCommit.from_commit(_column(keyring, valset, absent=(2, 3)))
    with pytest.raises(ErrNotEnoughVotingPower):
        verify_cert_trusting(CHAIN, valset, valset, two,
                             trust_level=(2, 3))


# ------------------------------------------------------------ blockstore ----
def test_blockstore_evidence_window(keyring, valset):
    """The full signature column survives only `full_commit_window`
    recent heights; the certificate stays canonical forever."""
    store = BlockStore(MemKV(), full_commit_window=2)
    executor = BlockExecutor(AppConns(KVStoreApp()))
    state = make_genesis_state(CHAIN, valset).copy()
    by_addr = {k.pub_key().address(): k for k in keyring}
    last = Commit()
    for h in range(1, 5):
        block = executor.create_proposal_block(
            h, state, last, state.validators.get_proposer().address,
            [b"x"], block_time=state.last_block_time)
        bid = block_id_for(block)
        vals_h = state.validators
        state = executor.apply_block(
            state, bid, block, last_commit_preverified=True)
        ts = Timestamp.from_unix_ns(
            state.last_block_time.unix_ns() + 1_000_000_000)
        msg = canonical_vote_bytes(
            SignedMsgType.PRECOMMIT, h, 0, bid, ts, CHAIN)
        column = Commit(height=h, round=0, block_id=bid, signatures=[
            CommitSig(BlockIDFlag.COMMIT, v.address, ts,
                      by_addr[v.address].sign(msg))
            for v in vals_h.validators])
        column.invalidate_memos()
        folded = fold_commit(column, vals_h)
        store.save_block(block, folded, full_seen_commit=column)
        last = folded
    # canonical reads are certificates at every height
    for h in range(1, 4):
        assert isinstance(store.load_block_commit(h), CertCommit)
    # full columns only inside the window (heights 3..4 of 4, window 2)
    assert store.load_seen_commit_full(1) is None
    assert store.load_seen_commit_full(2) is None
    full3 = store.load_seen_commit_full(3)
    full4 = store.load_seen_commit_full(4)
    assert isinstance(full3, Commit) and full3.size() == 4
    assert isinstance(full4, Commit) and full4.size() == 4
    assert not any(s.is_absent() for s in full4.signatures)


def test_blockstore_pre_cert_format_reads_unchanged(ed_chain):
    """Satellite back-compat: a seed-format (plain ed25519 column)
    store reads byte-identically through the shared decode path, and
    load_seen_commit_full falls back to the seen commit itself."""
    store, _state, _genesis, _signers = ed_chain
    for h in range(1, 5):
        seen = store.load_seen_commit(h)
        assert type(seen) is Commit
        assert getattr(seen, "cert", None) is None
        assert store.load_seen_commit_full(h).encode() == seen.encode()
        canon = store.load_block_commit(h)
        assert type(canon) is Commit
        # stored bytes are the plain-column encoding, bit for bit
        raw = store._db.get(b"SC:" + h.to_bytes(8, "big"))
        assert raw == seen.encode()


# ------------------------------------------------------------------ WAL ----
def test_wal_cert_frame_roundtrip(tmp_path, keyring, valset):
    from cometbft_tpu.consensus.wal import (
        WAL,
        AggregateCommitMessage,
        EndHeightMessage,
        MsgInfo,
    )

    cert = CertCommit.from_commit(_column(keyring, valset)).cert
    wal = WAL(str(tmp_path / "wal"))
    wal.write(MsgInfo(AggregateCommitMessage(cert), "peer-9"))
    wal.write(EndHeightMessage(7))
    wal.close()
    msgs = [m.msg for m in WAL(str(tmp_path / "wal")).read_all()]
    infos = [m for m in msgs if isinstance(m, MsgInfo)]
    assert len(infos) == 1 and infos[0].peer_id == "peer-9"
    assert isinstance(infos[0].msg, AggregateCommitMessage)
    assert infos[0].msg.cert == cert


# ----------------------------------------------- in-process all-BLS net ----
@pytest.mark.slow
def test_bls_net_commits_cert_native(tmp_path):
    """4 BLS validators reach consensus; every stored commit is a
    CertCommit that re-verifies against the validator set, catchup
    serves the certificate (not a reconstructed vote column), and the
    cert-gossip outcome classification behaves."""
    from cometbft_tpu.consensus.net import InProcessNetwork
    from cometbft_tpu.consensus.wal import AggregateCommitMessage
    from cometbft_tpu.utils.metrics import consensus_metrics

    net = InProcessNetwork(
        4, str(tmp_path), chain_id="bls-loop",
        key_type="tendermint/PubKeyBls12_381")
    vals = net.genesis.validators
    assert vals.all_bls()
    net.start()
    try:
        assert net.wait_for_height(4, timeout=120), "BLS net stalled"
    finally:
        net.stop()
    node = net.nodes[0]
    checked = 0
    for h in range(1, node.block_store.height() + 1):
        for commit in (node.block_store.load_seen_commit(h),
                       node.block_store.load_block_commit(h)):
            if commit is None:
                continue
            assert isinstance(commit, CertCommit), f"height {h}"
            commit.verify("bls-loop", vals)
            checked += 1
    assert checked >= 5  # >= 3 seen + >= 2 canonical at height >= 3
    # catchup: cert-native heights gossip the certificate, never a
    # reconstructed per-vote column
    cs = node.cs
    assert cs.cert_native

    def outcome(label):
        return consensus_metrics().cert_gossip_total.values().get(
            (label,), 0.0)

    cert1 = node.block_store.load_seen_commit(1).cert
    # stale: height long since committed
    before = outcome("stale")
    cs._handle_cert(AggregateCommitMessage(cert1), "peer-x")
    assert outcome("stale") == before + 1
    # disabled: the config gate short-circuits everything
    before = outcome("disabled")
    cs.cert_native = False
    cs._handle_cert(AggregateCommitMessage(cert1), "peer-x")
    cs.cert_native = True
    assert outcome("disabled") == before + 1
    # invalid: right height, garbage aggregate
    before = outcome("invalid")
    bogus = AggregateCommit(
        cs.height, 0, BID, TS,
        bytes([0x0F]) + b"\x00" * (len(cert1.bitmap) - 1),
        bytes(96))
    cs._handle_cert(AggregateCommitMessage(bogus), "peer-x")
    assert outcome("invalid") == before + 1


@pytest.mark.slow
def test_ed25519_net_reports_non_bls(tmp_path):
    """Cert gossip frames reaching a non-BLS chain are counted and
    dropped — the vote path is untouched."""
    from cometbft_tpu.consensus.net import InProcessNetwork
    from cometbft_tpu.consensus.wal import AggregateCommitMessage
    from cometbft_tpu.utils.metrics import consensus_metrics

    net = InProcessNetwork(1, str(tmp_path))
    net.start()
    try:
        assert net.wait_for_height(2, timeout=60)
    finally:
        net.stop()
    cs = net.nodes[0].cs

    def outcome(label):
        return consensus_metrics().cert_gossip_total.values().get(
            (label,), 0.0)

    before = outcome("non_bls")
    bogus = AggregateCommit(cs.height, 0, BID, TS, b"\x01", bytes(96))
    cs._handle_cert(AggregateCommitMessage(bogus), "peer-x")
    assert outcome("non_bls") == before + 1
    # and the stored commits are plain columns
    seen = net.nodes[0].block_store.load_seen_commit(1)
    assert type(seen) is Commit


# ---------------------------------------------------------------- light ----
@pytest.fixture(scope="module")
def cert_light_world(cert_chain):
    from cometbft_tpu.light import StoreProvider
    from cometbft_tpu.state.types import encode_validator_set

    store, state, _genesis = cert_chain
    ss = StateStore(MemKV())
    for h in range(1, 8):
        ss._db.set(b"SV:" + h.to_bytes(8, "big"),
                   encode_validator_set(state.validators))
    return StoreProvider(CHAIN, store, ss)


NOW = Timestamp.from_unix_ns(1_700_000_100_000_000_000)
PERIOD = 10**9


def test_light_verify_adjacent_cert(cert_light_world):
    from cometbft_tpu.light import verify_adjacent

    p = cert_light_world
    t, u = p.light_block(2), p.light_block(3)
    assert getattr(u.signed_header.commit, "cert", None) is not None
    verify_adjacent(CHAIN, t.signed_header, u.signed_header, u.validators,
                    PERIOD, NOW, backend="cpu")
    # tampered aggregate hard-fails the adjacent step
    cc = u.signed_header.commit
    bad = CertCommit(
        AggregateCommit(cc.cert.height, cc.cert.round, cc.cert.block_id,
                        cc.cert.timestamp, cc.cert.bitmap,
                        bytes([cc.cert.agg_sig[0] ^ 0xFF])
                        + cc.cert.agg_sig[1:]),
        cc.size_)
    from cometbft_tpu.light import SignedHeader

    with pytest.raises(ErrInvalidSignature):
        verify_adjacent(CHAIN, t.signed_header,
                        SignedHeader(u.signed_header.header, bad),
                        u.validators, PERIOD, NOW, backend="cpu")


def test_light_verify_non_adjacent_cert(cert_light_world):
    """Skipping verification over a certificate pivot: one pairing
    covers the trust tally and the +2/3 check."""
    from cometbft_tpu.light import verify_non_adjacent
    from cometbft_tpu.light.verifier import ErrNewValSetCantBeTrusted

    p = cert_light_world
    t, u = p.light_block(1), p.light_block(5)
    trusted_next = p.light_block(2).validators
    pc0 = bls.pairing_checks()
    verify_non_adjacent(CHAIN, t.signed_header, trusted_next,
                        u.signed_header, u.validators, PERIOD, NOW,
                        backend="cpu")
    assert bls.pairing_checks() - pc0 == 1
    # a trust shortfall maps to the bisection trigger, not a hard fail
    weak = ValidatorSet([
        Validator.from_pub_key(
            bls.BlsPrivKey.from_secret(b"stranger-%d" % i).pub_key(), 10)
        for i in range(4)
    ])
    with pytest.raises(ErrNewValSetCantBeTrusted):
        verify_non_adjacent(CHAIN, t.signed_header, weak,
                            u.signed_header, u.validators, PERIOD, NOW,
                            backend="cpu")


def test_light_verify_stream_cert(cert_light_world):
    from cometbft_tpu.light import verify_stream

    p = cert_light_world
    stream = [p.light_block(h) for h in range(2, 7)]
    verify_stream(CHAIN, p.light_block(1), stream, PERIOD, NOW,
                  backend="cpu")


# ------------------------------------------------------------ feed/replay ----
def test_feed_frames_cert_native(cert_chain, valset):
    import json

    from cometbft_tpu.replication.feed import ReplicationFeed

    store, _state, _genesis = cert_chain

    class _Vals:
        def load_validators(self, h):
            return valset

    feed = ReplicationFeed(CHAIN, store, _Vals())
    frame = json.loads(feed._build_frame(store.load_block(4)))
    assert frame["cert"]["kind"] == "cert_native"
    assert isinstance(
        decode_commit_any(bytes.fromhex(frame["last"])), CertCommit)
    assert isinstance(
        decode_commit_any(bytes.fromhex(frame["seen"])), CertCommit)
    cert = AggregateCommit.decode(bytes.fromhex(frame["cert"]["data"]))
    assert cert.signer_count() == 4


def test_replay_cert_chain_accept_and_reject(cert_chain, valset):
    from cometbft_tpu.blocksync import ReplayEngine

    store, state, genesis = cert_chain
    # one window for the whole chain: a window boundary re-verifies the
    # boundary commit (each window checks its own tip), which would skew
    # the exact per-certificate arithmetic below
    engine = ReplayEngine(
        store, BlockExecutor(AppConns(KVStoreApp())),
        verify_mode="batched", window=8)
    pc0 = bls.pairing_checks()
    replayed, stats = engine.run(genesis.copy())
    assert replayed.last_block_height == 6
    assert replayed.app_hash == state.app_hash
    assert stats.sigs_verified == 6 * 4  # signer_count per certificate
    assert bls.pairing_checks() - pc0 == 6  # ONE pairing per commit
    # corrupting one stored certificate fails that replay
    bad_store = BlockStore(MemKV())
    for h in range(1, 7):
        raw = store._db.get(b"B:" + h.to_bytes(8, "big"))
        bad_store._db.set(b"B:" + h.to_bytes(8, "big"), raw)
        sc = store._db.get(b"SC:" + h.to_bytes(8, "big"))
        if h == 4:
            cc = decode_commit_any(sc)
            sc = CertCommit(
                AggregateCommit(cc.cert.height, cc.cert.round,
                                cc.cert.block_id, cc.cert.timestamp,
                                cc.cert.bitmap,
                                bytes([cc.cert.agg_sig[0] ^ 0xFF])
                                + cc.cert.agg_sig[1:]),
                cc.size_).encode()
        bad_store._db.set(b"SC:" + h.to_bytes(8, "big"), sc)
    bad_store._base, bad_store._height = 1, 6
    bad = ReplayEngine(
        bad_store, BlockExecutor(AppConns(KVStoreApp())),
        verify_mode="batched", window=4)
    with pytest.raises(Exception):
        bad.run(genesis.copy())


# ------------------------------------------------------------- manifest ----
def test_manifest_key_type():
    from cometbft_tpu.e2e.manifest import Manifest, generate_manifest

    assert Manifest.parse({}).key_type == "ed25519"
    assert Manifest.parse({"key_type": "bls"}).key_type == "bls"
    kinds = {generate_manifest(seed).key_type for seed in range(30)}
    assert kinds == {"ed25519", "bls"}
