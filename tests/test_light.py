"""Light client tests (reference light/verifier_test.go, client_test.go)."""

import pytest

from cometbft_tpu.light import (
    ErrHeaderExpired,
    ErrInvalidHeader,
    LightBlock,
    LightClient,
    LightStore,
    SignedHeader,
    StoreProvider,
    verify_adjacent,
    verify_non_adjacent,
    verify_stream,
)
from cometbft_tpu.light.client import ErrConflictingHeaders
from cometbft_tpu.storage import MemKV, StateStore
from cometbft_tpu.types import Timestamp
from cometbft_tpu.types.validation import ErrInvalidSignature
from cometbft_tpu.utils.factories import make_chain

CHAIN = "light-chain"
NOW = Timestamp.from_unix_ns(1_700_000_100_000_000_000)
PERIOD = 10**9  # practically unexpiring for tests


@pytest.fixture(scope="module")
def chain():
    from cometbft_tpu.state.types import encode_validator_set

    store, state, genesis, signers = make_chain(
        12, n_validators=4, chain_id=CHAIN, backend="cpu"
    )
    ss = StateStore(MemKV())
    # save per-height validator sets (constant set in this chain)
    for h in range(1, 13):
        ss._db.set(
            b"SV:" + h.to_bytes(8, "big"), encode_validator_set(state.validators)
        )
    return store, state, ss


def _provider(chain):
    store, state, ss = chain
    return StoreProvider(CHAIN, store, ss)


def _lb(provider, h):
    lb = provider.light_block(h)
    assert lb is not None, h
    return lb


def test_provider_and_basic_validate(chain):
    p = _provider(chain)
    lb = _lb(p, 3)
    lb.basic_validate(CHAIN)


def test_verify_adjacent_ok_and_expired(chain):
    p = _provider(chain)
    t, u = _lb(p, 3), _lb(p, 4)
    verify_adjacent(
        CHAIN, t.signed_header, u.signed_header, u.validators, PERIOD, NOW,
        backend="cpu",
    )
    with pytest.raises(ErrHeaderExpired):
        verify_adjacent(
            CHAIN, t.signed_header, u.signed_header, u.validators, 1, NOW,
            backend="cpu",
        )


def test_verify_adjacent_rejects_tampering(chain):
    p = _provider(chain)
    t, u = _lb(p, 3), _lb(p, 4)
    bad = SignedHeader(u.signed_header.header, u.signed_header.commit)
    sig0 = bad.commit.signatures[0]
    orig = sig0.signature
    sig0.signature = bytes(64)
    # the commit came through Commit.decode: a slot mutated in place
    # must drop the decode memos (Commit.invalidate_memos' contract)
    bad.commit.invalidate_memos()
    with pytest.raises(ErrInvalidSignature):
        verify_adjacent(
            CHAIN, t.signed_header, bad, u.validators, PERIOD, NOW,
            backend="cpu",
        )
    sig0.signature = orig


def test_verify_non_adjacent(chain):
    p = _provider(chain)
    t, u = _lb(p, 2), _lb(p, 9)
    verify_non_adjacent(
        CHAIN, t.signed_header, _lb(p, 3).validators, u.signed_header,
        u.validators, PERIOD, NOW, backend="cpu",
    )


def test_verify_stream_and_corruption(chain):
    p = _provider(chain)
    trusted = _lb(p, 1)
    stream = [_lb(p, h) for h in range(2, 11)]
    verify_stream(CHAIN, trusted, stream, PERIOD, NOW, backend="cpu")
    # corrupt one NIL... one COMMIT signature mid-stream
    victim = stream[4].signed_header.commit.signatures[2]
    orig = victim.signature
    victim.signature = orig[:-1] + bytes([orig[-1] ^ 1])
    stream[4].signed_header.commit.invalidate_memos()
    with pytest.raises(ErrInvalidSignature):
        verify_stream(CHAIN, trusted, stream, PERIOD, NOW, backend="cpu")
    victim.signature = orig


def test_client_bisection_and_store(chain):
    p = _provider(chain)
    anchor = _lb(p, 1)
    c = LightClient(CHAIN, p, store=LightStore(), trusting_period_s=PERIOD,
                    backend="cpu")
    c.initialize(1, anchor.signed_header.header.hash())
    out = c.verify_to_height(11, NOW)
    assert out.height == 11
    assert c.store.latest().height == 11
    # idempotent: verified heights are served from the store
    again = c.verify_to_height(11, NOW)
    assert again.signed_header.header.hash() == out.signed_header.header.hash()


def test_client_sequential(chain):
    p = _provider(chain)
    anchor = _lb(p, 1)
    c = LightClient(CHAIN, p, store=LightStore(), trusting_period_s=PERIOD,
                    backend="cpu", skipping=False)
    c.initialize(1, anchor.signed_header.header.hash())
    out = c.verify_to_height(6, NOW)
    assert out.height == 6
    assert set(c.store.heights()) == {1, 2, 3, 4, 5, 6}


def test_client_drops_unsubstantiated_witness(chain):
    """A witness that serves a tampered header it cannot back with a
    verifying chain is DROPPED, not treated as an attack (reference
    light/detector.go: examination failure removes the witness)."""
    p = _provider(chain)

    class LyingWitness(StoreProvider):
        def light_block(self, height):
            lb = super().light_block(height)
            if lb and height == 7:
                lb.signed_header.header.app_hash = b"\xde\xad" * 16
            return lb

    store, state, ss = chain
    w = LyingWitness(CHAIN, store, ss)
    anchor = _lb(p, 1)
    c = LightClient(CHAIN, p, witnesses=[w], store=LightStore(),
                    trusting_period_s=PERIOD, backend="cpu")
    c.initialize(1, anchor.signed_header.header.hash())
    out = c.verify_to_height(7, NOW)
    assert out.height == 7
    assert c.witnesses == []  # liar demoted


def test_client_detects_real_fork(chain):
    """A witness backing a conflicting chain SIGNED BY THE SAME
    VALIDATORS is a light-client attack: ErrConflictingHeaders with
    LightClientAttackEvidence naming the double-signers (reference
    light/detector.go + types/evidence.go GetByzantineValidators)."""
    from cometbft_tpu.state.types import encode_validator_set
    from cometbft_tpu.storage import MemKV, StateStore

    p = _provider(chain)
    # fork: same signers (same seed), different transactions
    store2, state2, _genesis2, _signers2 = make_chain(
        12, n_validators=4, chain_id=CHAIN, backend="cpu", txs_per_block=3
    )
    ss2 = StateStore(MemKV())
    for h in range(1, 13):
        ss2._db.set(
            b"SV:" + h.to_bytes(8, "big"),
            encode_validator_set(state2.validators),
        )
    w = StoreProvider(CHAIN, store2, ss2)
    received = []
    w.report_evidence = received.append
    anchor = _lb(p, 1)
    c = LightClient(CHAIN, p, witnesses=[w], store=LightStore(),
                    trusting_period_s=PERIOD, backend="cpu")
    c.initialize(1, anchor.signed_header.header.hash())
    with pytest.raises(ErrConflictingHeaders) as ei:
        c.verify_to_height(7, NOW)
    ev = ei.value.evidence
    assert ev is not None
    assert ev.common_height >= 1
    assert len(ev.byzantine_validators) >= 3  # all four signed both chains
    assert ev.conflicting_block.height == 7
    # both directions reported (reference examines the primary's trace
    # too): the witness — whose chain may be the canonical one — must
    # receive evidence naming the PRIMARY's block, or a lying primary
    # would halt the client without ever being prosecutable
    primary_hash = _lb(p, 7).signed_header.header.hash()
    assert any(
        e.conflicting_block.signed_header.header.hash() == primary_hash
        for e in received
    ), "witness never got primary-direction evidence"
