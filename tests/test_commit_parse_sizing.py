"""The native commit parser's buffers follow the commit's signature count.

`native.commit_parse` asks `commit_count` (csrc/commit_codec.inc) how
many signature slots a wire buffer holds and allocates its eight columns
for exactly that many. Until PR 47 it allocated for the most a buffer of
that length could hold (`len(buf) // 6 + 4`: 17,516 slots for a commit of
1,000 signatures), 3.7 MB asked of the C allocator a decode and 2 MB of it
kept alive with the commit. These tests hold the sizes to the count and
the decode to the pure-Python walk at sizes and shapes the differential
fuzz (test_commit_codec_diff.py, 0 to 7 slots) never reaches.
"""

from __future__ import annotations

import ctypes
import random
import tracemalloc
import zlib
from unittest import mock

import numpy as np
import pytest

from cometbft_tpu.crypto import native
from cometbft_tpu.types.basic import BlockID, PartSetHeader, Timestamp
from cometbft_tpu.types.block import BlockIDFlag, Commit, CommitSig

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native lib unavailable (nothing to size)"
)

CHAIN_ID = "sizing-chain"
# what a column may hold beyond its slots (today nothing: the columns
# are allocated by size); a constant, whatever the commit holds
SLACK_BYTES = 64
# bytes a slot takes in all seven kept columns: flag 1, address length 1,
# address 20, seconds 8, nanos 8, signature length 1, signature 64
KEPT_BYTES_PER_SLOT = 1 + 1 + 20 + 8 + 8 + 1 + 64
# ... and in everything one parse allocates: the eight ctypes columns
# (the kept seven and 16 bytes of span) and the five `.raw` copies
PARSE_BYTES_PER_SLOT = KEPT_BYTES_PER_SLOT + 16 + (1 + 1 + 20 + 1 + 64)


def _signed(rng: random.Random, flag=BlockIDFlag.COMMIT) -> CommitSig:
    return CommitSig(
        block_id_flag=flag,
        validator_address=rng.randbytes(20),
        timestamp=Timestamp(1_700_000_000 + rng.randrange(1000),
                            rng.randrange(1_000_000_000)),
        signature=rng.randbytes(64),
    )


def _slots(kind: str, n: int, rng: random.Random) -> list:
    if kind == "signed":
        return [_signed(rng) for _ in range(n)]
    if kind == "absent":
        return [CommitSig.absent() for _ in range(n)]
    assert kind == "mixed"
    out = []
    for _ in range(n):
        r = rng.random()
        if r < 0.3:
            out.append(CommitSig.absent())
        elif r < 0.4:
            out.append(_signed(rng, BlockIDFlag.NIL))
        else:
            out.append(_signed(rng))
    return out


def _wire(kind: str, n: int) -> bytes:
    rng = random.Random(zlib.crc32(f"{kind}-{n}".encode()))
    bid = BlockID(rng.randbytes(32), PartSetHeader(3, rng.randbytes(32)))
    return Commit(height=1234, round=1, block_id=bid,
                  signatures=_slots(kind, n, rng)).encode()


def _decode_python(buf: bytes, trusted: bool) -> Commit:
    with mock.patch.object(native, "available", return_value=False):
        return Commit.decode(buf, trusted_bytes=trusted)


def _nbytes(col) -> int:
    return ctypes.sizeof(col) if isinstance(col, ctypes.Array) else len(col)


CASES = [
    ("signed", 1),
    ("signed", 150),
    ("signed", 1000),
    ("signed", 10000),
    # 4 bytes an entry on the wire: the shape the old divisor was for,
    # and the one a guess from the wire's length would get most wrong
    ("absent", 1000),
    ("mixed", 1000),
]


@pytest.mark.parametrize("kind,n", CASES, ids=[f"{k}-{n}" for k, n in CASES])
def test_decode_is_sized_by_the_count_and_equals_the_python_walk(kind, n):
    buf = _wire(kind, n)

    # (a) the columns kept with the commit are as long as n slots need
    nat = Commit.decode(buf, trusted_bytes=True)
    cols = nat.__dict__["_cols"]
    assert cols[0] == n
    widths = (1, 1, 20, 8, 8, 1, 64)  # flags .. sigs, as Commit._cols has them
    for col, width in zip(cols[1:], widths):
        assert _nbytes(col) <= n * width + SLACK_BYTES, (
            f"a {width}-byte column of {n} slots holds {_nbytes(col)} bytes"
        )
    kept = sum(_nbytes(c) for c in cols[1:])
    assert kept <= n * KEPT_BYTES_PER_SLOT + 7 * SLACK_BYTES
    # the wire spans are the parse's, not the commit's
    assert len(cols) == 8

    # (b) field for field what the Python walk gives, both ways of decoding
    for trusted in (False, True):
        py = _decode_python(buf, trusted)
        nt = Commit.decode(buf, trusted_bytes=trusted)
        assert "_cols" not in py.__dict__ and "_cols" in nt.__dict__
        assert (py.height, py.round, py.block_id) == (
            nt.height, nt.round, nt.block_id)
        assert py.__dict__.get("_sig_spans") == nt.__dict__.get("_sig_spans")
        assert (py.__dict__.get("_sig_spans") is not None) == trusted
        assert py.hash() == nt.hash()
        assert len(nt.signatures) == n
        assert py.signatures == nt.signatures
        assert nt.encode() == buf

    # (c) the columns batch verification reads, against the slots
    py = _decode_python(buf, False)
    flags, addrs, addr_lens, sig_lens, sigs, ts_s, ts_n = nat.verify_columns()
    assert flags.shape == (n,) and addrs.shape == (n, 20)
    assert sigs.shape == (n, 64) and ts_s.shape == ts_n.shape == (n,)
    assert flags.tolist() == [int(cs.block_id_flag) for cs in py.signatures]
    assert addr_lens.tolist() == [len(cs.validator_address)
                                  for cs in py.signatures]
    assert sig_lens.tolist() == [len(cs.signature) for cs in py.signatures]
    assert addrs.tobytes() == b"".join(
        cs.validator_address.ljust(20, b"\0") for cs in py.signatures)
    assert sigs.tobytes() == b"".join(
        cs.signature.ljust(64, b"\0") for cs in py.signatures)
    assert ts_s.tolist() == [cs.timestamp.seconds for cs in py.signatures]
    assert ts_n.tolist() == [cs.timestamp.nanos for cs in py.signatures]

    # (d) the sign bytes of every slot in one blob, against one at a time
    blob, lens = nat.vote_sign_bytes_blob(CHAIN_ID)
    want = [
        b"" if cs.block_id_flag == BlockIDFlag.ABSENT
        else py.vote_sign_bytes(CHAIN_ID, i)
        for i, cs in enumerate(py.signatures)
    ]
    assert np.asarray(lens).tolist() == [len(w) for w in want]
    assert bytes(blob) == b"".join(want)


@pytest.mark.parametrize("kind,n", CASES, ids=[f"{k}-{n}" for k, n in CASES])
def test_a_parse_asks_the_allocator_for_what_the_count_needs(kind, n):
    """Everything one `commit_parse` allocates (the eight columns and the
    `.raw` copies), by the interpreter's own account of its allocators:
    under 0.3 MB for 1,000 signatures where it was 3.7 MB."""
    buf = _wire(kind, n)
    native.commit_parse(buf)  # the array types ctypes makes once a size
    tracemalloc.start()
    try:
        base = tracemalloc.get_traced_memory()[0]
        tracemalloc.reset_peak()
        parsed = native.commit_parse(buf)
        peak = tracemalloc.get_traced_memory()[1] - base
    finally:
        tracemalloc.stop()
    assert parsed is not None and parsed[3][0] == n
    assert len(parsed[3][8]) == 2 * n  # the spans
    assert peak <= n * PARSE_BYTES_PER_SLOT + 4096, peak
    if (kind, n) == ("signed", 1000):
        assert peak < 300_000


def test_count_is_what_a_parse_of_that_capacity_fills():
    """`commit_count` against `commit_parse` on whole, cut and altered
    wires: where the count is n the parse fills n or refuses (-1, an entry
    it cannot take), never asks for more room (-2); where the top-level
    walk fails both refuse, and `native.commit_parse` gives None so that
    the Python walk raises what it raises."""
    lib = native.get_lib()
    rng = random.Random(47)
    wire = _wire("mixed", 40)
    bufs = [wire, b"", wire + wire]
    bufs += [wire[:rng.randrange(len(wire))] for _ in range(200)]
    for _ in range(400):
        mut = bytearray(wire)
        for _ in range(rng.randrange(1, 5)):
            mut[rng.randrange(len(mut))] = rng.randrange(256)
        bufs.append(bytes(mut))
    counted = refused = 0
    for buf in bufs:
        n = lib.commit_count(buf, len(buf))
        parsed = native.commit_parse(buf)
        if n < 0:
            assert n == -1 and parsed is None
            with pytest.raises(ValueError):
                _decode_python(buf, False)
            refused += 1
            continue
        counted += 1
        # one slot fewer than the count is too few, the count is enough
        cap = max(n - 1, 0)
        rc = _raw_parse(lib, buf, cap)
        assert rc in ((-2, -1) if n else (0, -1))
        rc = _raw_parse(lib, buf, n)
        assert rc in (n, -1)
        assert (parsed is None) == (rc == -1)
        if parsed is not None:
            assert parsed[3][0] == n
    assert counted > 100 and refused > 100


def _raw_parse(lib, buf: bytes, cap: int) -> int:
    head = (ctypes.c_uint64 * 4)()
    return lib.commit_parse(
        buf, len(buf), cap, head,
        ctypes.create_string_buffer(cap), ctypes.create_string_buffer(cap),
        ctypes.create_string_buffer(cap * 20),
        (ctypes.c_int64 * cap)(), (ctypes.c_int64 * cap)(),
        ctypes.create_string_buffer(cap), ctypes.create_string_buffer(cap * 64),
        (ctypes.c_uint64 * (cap * 2))(),
    )
