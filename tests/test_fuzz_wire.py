"""Wire-decoder fuzzing + flow-rate enforcement (reference test/fuzz/
and p2p/conn/connection.go:43-44).

Every p2p-facing decoder must survive arbitrary mutations of valid
messages — truncations, bit flips, random garbage — by either decoding
to SOME value or raising a normal exception. A hang or interpreter
error fails the test harness itself; this is the Python analogue of the
reference's go-fuzz corpus over the consensus/p2p/mempool decoders."""

import random

import pytest

from cometbft_tpu.consensus.reactor import (
    BlockPartMessage,
    HasVoteMessage,
    NewRoundStepMessage,
    NewValidBlockMessage,
    VoteSetBitsMessage,
    VoteSetMaj23Message,
    decode_consensus_msg,
    encode_consensus_msg,
)
from cometbft_tpu.crypto import merkle
from cometbft_tpu.p2p.pex import (
    NetAddress,
    decode_pex_message,
    encode_pex_addrs,
    encode_pex_request,
)
from cometbft_tpu.statesync import messages as ssm
from cometbft_tpu.types import Timestamp, Vote
from cometbft_tpu.types.basic import BlockID, PartSetHeader
from cometbft_tpu.types.evidence import decode_evidence
from cometbft_tpu.types.part_set import Part
from cometbft_tpu.types.vote import SignedMsgType

N_MUTATIONS = 300


def _mutations(rng, data: bytes):
    yield b""
    yield data
    for _ in range(N_MUTATIONS):
        kind = rng.randrange(4)
        if kind == 0 and data:  # truncate
            yield data[: rng.randrange(len(data))]
        elif kind == 1 and data:  # bit flip
            i = rng.randrange(len(data))
            yield data[:i] + bytes([data[i] ^ (1 << rng.randrange(8))]) + data[i + 1:]
        elif kind == 2:  # random garbage
            yield rng.randbytes(rng.randrange(1, 64))
        else:  # splice two halves at a random point
            i = rng.randrange(len(data) + 1)
            yield data[i:] + data[:i]


def _fuzz(decoder, seeds, seed=1234):
    rng = random.Random(seed)
    survived = 0
    for valid in seeds:
        for mut in _mutations(rng, valid):
            try:
                decoder(mut)
            except Exception:  # noqa: BLE001 — clean rejection is the point
                pass
            survived += 1
    assert survived > N_MUTATIONS  # the loop genuinely ran


def _sample_vote():
    return Vote(
        type=SignedMsgType.PRECOMMIT, height=7, round=1,
        block_id=BlockID(hash=b"\xaa" * 32,
                         part_set_header=PartSetHeader(3, b"\xbb" * 32)),
        timestamp=Timestamp(1, 2), validator_address=b"\x01" * 20,
        validator_index=2, signature=b"\x02" * 64,
    )


def test_fuzz_consensus_decoder():
    part = Part(index=0, bytes_=b"block-part-payload",
                proof=merkle.Proof(total=1, index=0,
                                   leaf_hash=b"\xcc" * 32, aunts=[]))
    seeds = [
        encode_consensus_msg(m)
        for m in (
            NewRoundStepMessage(7, 1, 3, 0),
            HasVoteMessage(7, 1, SignedMsgType.PREVOTE, 4),
            BlockPartMessage(7, 1, part),
            NewValidBlockMessage(7, 1, PartSetHeader(3, b"\xbb" * 32), True),
            VoteSetMaj23Message(7, 1, SignedMsgType.PREVOTE,
                                BlockID(hash=b"\xaa" * 32)),
            VoteSetBitsMessage(7, 1, SignedMsgType.PREVOTE,
                               BlockID(hash=b"\xaa" * 32), (1 << 100) | 5),
        )
    ]
    _fuzz(decode_consensus_msg, seeds)


def test_fuzz_pex_decoder():
    seeds = [
        encode_pex_request(),
        encode_pex_addrs([NetAddress("aa" * 20, "127.0.0.1", 26656)]),
        # richer shapes: empty list, empty-field addr, IPv6 + port
        # edges, and a full MAX_ADDRS_PER_MSG-sized message
        encode_pex_addrs([]),
        encode_pex_addrs([NetAddress("", "", 0)]),
        encode_pex_addrs([
            NetAddress("bb" * 20, "::1", 1),
            NetAddress("cc" * 20, "2001:db8::42", 65535),
            NetAddress("dd" * 20, "seed.example.com", 26656),
        ]),
        encode_pex_addrs([
            NetAddress(f"{i:040x}", f"10.0.{i // 256}.{i % 256}", 26656)
            for i in range(100)
        ]),
    ]
    _fuzz(decode_pex_message, seeds)


def test_pex_decoder_nested_garbage():
    """Hand-crafted malformations beyond random mutation: nested
    length-prefix lies, wrong wire types, and huge varint ports must be
    rejected or decoded — never hang or corrupt (the decoder fronts
    channel 0x00, reachable pre-authorization by any dialer)."""
    from cometbft_tpu.encoding import proto as pb

    cases = [
        pb.f_embedded(2, pb.f_embedded(1, b"\xff" * 40)),  # garbage addr
        pb.f_embedded(2, pb.f_embedded(1, pb.f_embedded(1, pb.f_embedded(
            1, b"\x08\x01")))),  # over-nesting
        pb.f_embedded(2, pb.f_varint(1, 7)),  # addr as varint, not bytes
        pb.f_varint(1, 1 << 62),  # request field with a huge varint
        pb.f_embedded(2, pb.f_embedded(
            1, pb.f_string(1, "id") + pb.f_varint(3, 1 << 63))),  # port
        pb.f_embedded(1, b"") + pb.f_embedded(2, b""),  # both oneof arms
        b"\xff" * 10,  # bare continuation bits
    ]
    for raw in cases:
        try:
            kind, addrs = decode_pex_message(raw)
        except Exception:  # noqa: BLE001 — clean rejection is fine
            continue
        assert kind in (None, "request", "addrs")
        if kind == "addrs":
            for a in addrs:
                assert isinstance(a, NetAddress)


def test_fuzz_statesync_decoder():
    seeds = [
        ssm.SnapshotsRequest().encode(),
        ssm.ChunkRequest(8, 1, 0).encode(),
    ]
    _fuzz(ssm.decode_message, seeds)


def test_fuzz_evidence_decoder():
    from cometbft_tpu.types.evidence import DuplicateVoteEvidence

    ev = DuplicateVoteEvidence.from_votes(
        _sample_vote(), _sample_vote(), 10, 40, Timestamp(1, 0)
    )
    _fuzz(decode_evidence, [ev.wrapped()])


def test_fuzz_vote_decoder():
    _fuzz(Vote.decode, [_sample_vote().encode()])


def _finalize_resp(results: str, updates: int):
    """A FinalizeBlockResponse of a seeded shape: `results` = none, plain
    (code 0 and a value, as the kvstore answers), rich (data, logs,
    non-zero codes, gas wanted and used, negative gas, non-ASCII logs) or
    loaded (400 results of 1 KB, the QA load's block)."""
    from cometbft_tpu.abci import types as T

    rng = random.Random(f"{results}/{updates}")
    if results == "none":
        txs = []
    elif results == "plain":
        txs = [T.ExecTxResult(code=0, data=rng.randbytes(rng.randrange(40)))
               for _ in range(12)]
    elif results == "loaded":
        txs = [T.ExecTxResult(code=0, data=rng.randbytes(1022))
               for _ in range(400)]
    else:
        txs = [T.ExecTxResult(
            code=rng.choice((0, 1, 2, 130, 1 << 20)),
            data=rng.randbytes(rng.choice((0, 1, 127, 128, 300))),
            log=rng.choice(("", "ok", "insufficient funds: 7 < 9",
                            "pr\u00fcfung \u2713", "x" * 200)),
            gas_wanted=rng.choice((0, 1, 200_000, -1)),
            gas_used=rng.choice((0, 21_000, 1 << 40)))
            for _ in range(40)]
    vus = [T.ValidatorUpdate(
        pub_key_bytes=rng.randbytes(33 if i % 3 == 1 else 32),
        pub_key_type="secp256k1" if i % 3 == 1 else "ed25519",
        power=rng.choice((0, 1, 100, 1 << 50))) for i in range(updates)]
    return T.FinalizeBlockResponse(
        tx_results=txs, validator_updates=vus,
        app_hash=b"" if results == "none" else rng.randbytes(32))


@pytest.mark.parametrize("updates", (0, 1, 5))
@pytest.mark.parametrize("results", ("none", "plain", "rich", "loaded"))
def test_finalize_response_encodes_as_the_reference_and_its_decoder_survives(
        results, updates):
    """abci/wire.enc_finalize_resp (the state store's AR: record and the
    socket server's answer) joins its parts: the bytes are those of the
    plain encoder that grows one `bytes` field by field, they decode back
    to the response, and the decoder survives their mutations."""
    import state_encoding_reference as ref

    from cometbft_tpu.abci import wire

    resp = _finalize_resp(results, updates)
    enc = wire.enc_finalize_resp(resp)
    assert enc == ref.finalize_resp(resp)
    def rows(r):
        return (r.app_hash,
                [(t.code, t.data, t.log, t.gas_wanted, t.gas_used)
                 for t in r.tx_results],
                [(v.pub_key_bytes, v.pub_key_type, v.power)
                 for v in r.validator_updates])

    back = wire.dec_finalize_resp(enc)
    assert rows(back) == rows(resp)
    assert wire.enc_finalize_resp(back) == enc
    if results != "loaded":  # (300 mutations of 411 KB say nothing more)
        _fuzz(wire.dec_finalize_resp, [enc])


def test_mconnection_rate_enforcement():
    """A 20 KiB burst over a 64 KB/s send-limited conn must take ~300ms;
    with limits off it completes near-instantly (reference flowrate
    Limit() backpressure)."""
    import threading
    import time

    from cometbft_tpu.p2p.conn import ChannelDescriptor, MConnection

    class Pipe:
        """In-memory duplex message pipe."""

        def __init__(self):
            self.q = None

        @staticmethod
        def pair():
            import queue

            a, b = Pipe(), Pipe()
            a._out, b._out = queue.Queue(), queue.Queue()
            a._in, b._in = b._out, a._out
            return a, b

        def write_msg(self, m):
            self._out.put(bytes(m))

        def read_msg(self):
            m = self._in.get()
            if m is None:
                raise ConnectionError("closed")
            return m

        def close(self):
            self._out.put(None)

    def run_once(rate):
        a, b = Pipe.pair()
        descs = [ChannelDescriptor(0x30)]
        done = threading.Event()
        total = {"n": 0}

        def on_recv(c, m):
            total["n"] += len(m)
            if total["n"] >= 20_000:
                done.set()

        ma = MConnection(a, descs, lambda c, m: None, send_rate=rate,
                         recv_rate=0)
        mb = MConnection(b, descs, on_recv, send_rate=0, recv_rate=0)
        ma.start()
        mb.start()
        t0 = time.monotonic()
        try:
            for _ in range(20):
                ma.send(0x30, b"z" * 1000)
            assert done.wait(15), "transfer incomplete"
            return time.monotonic() - t0
        finally:
            ma.stop()
            mb.stop()

    fast = run_once(0)
    slow = run_once(32_000)  # 20 KiB at 32 KB/s: ~0.6 s of budget waits
    assert slow > 0.3, f"rate limit not enforced: {slow:.3f}s"
    assert slow > 3 * fast, f"no separation: fast={fast:.3f}s slow={slow:.3f}s"


def test_commit_sig_span_overrun_rejected():
    """A CommitSig span ending mid-varint (continuation bit set at the
    span boundary) must raise, not silently consume the next field's
    bytes — the specialized span decoder must match the generic
    sub-buffer decoder's strictness."""
    import pytest

    from cometbft_tpu.encoding import proto as pb
    from cometbft_tpu.types.block import Commit

    # commit with one malformed sig entry: field1 varint whose last
    # byte keeps the continuation bit, followed by a second sig entry
    bad_sig = b"\x08\xff"  # field 1 varint, truncated (cont. bit set)
    good_sig = pb.f_varint(1, 2) + pb.f_bytes(2, b"a" * 20) + pb.f_bytes(4, b"s" * 64)
    buf = (
        pb.f_varint(1, 5)
        + pb.f_embedded(4, bad_sig)
        + pb.f_embedded(4, good_sig)
    )
    with pytest.raises(ValueError):
        Commit.decode(buf)
