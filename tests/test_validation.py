"""VerifyCommit family tests over real signed commits (TPU batch path)."""

import pytest

from cometbft_tpu.types import validation
from cometbft_tpu.types.block import BlockIDFlag
from cometbft_tpu.utils import factories as fx

CHAIN = "test-chain"


def _setup(n=6, powers=None, absent=None, height=5):
    signers = fx.make_signers(n, seed=11)
    vals = fx.make_validator_set(signers, powers)
    by_addr = {s.address(): s for s in signers}
    bid = fx.make_block_id(b"blk-%d" % height)
    commit = fx.make_commit(CHAIN, height, 0, bid, vals, by_addr, absent=absent)
    return signers, vals, bid, commit


def test_verify_commit_ok():
    _, vals, bid, commit = _setup()
    validation.verify_commit(CHAIN, vals, bid, 5, commit)
    validation.verify_commit_light(CHAIN, vals, bid, 5, commit)
    validation.verify_commit_light_trusting(CHAIN, vals, commit)


def test_verify_commit_wrong_height_and_blockid():
    _, vals, bid, commit = _setup()
    with pytest.raises(validation.ErrInvalidCommitHeight):
        validation.verify_commit(CHAIN, vals, bid, 6, commit)
    with pytest.raises(validation.ErrInvalidBlockID):
        validation.verify_commit(CHAIN, vals, fx.make_block_id(b"other"), 5, commit)


def test_verify_commit_bad_signature_located():
    _, vals, bid, commit = _setup()
    sig = bytearray(commit.signatures[2].signature)
    sig[1] ^= 0xFF
    commit.signatures[2].signature = bytes(sig)
    with pytest.raises(validation.ErrInvalidSignature) as ei:
        validation.verify_commit(CHAIN, vals, bid, 5, commit)
    assert "index 2" in str(ei.value)


def test_verify_commit_absent_below_threshold():
    # 6 validators, 3 absent: tally 30/60 <= 2/3 threshold -> fail
    _, vals, bid, commit = _setup(absent={0, 1, 2})
    with pytest.raises(validation.ErrNotEnoughVotingPower):
        validation.verify_commit(CHAIN, vals, bid, 5, commit)


def test_verify_commit_absent_above_threshold():
    # 1 absent of 6: 50/60 > 2/3 -> ok
    _, vals, bid, commit = _setup(absent={4})
    validation.verify_commit(CHAIN, vals, bid, 5, commit)
    validation.verify_commit_light(CHAIN, vals, bid, 5, commit)


def test_nil_votes_verified_but_not_counted():
    _, vals, bid, commit = _setup()
    # flip one COMMIT slot to NIL: its signature no longer matches (it signed
    # the block id), so full verification must fail on that slot...
    commit.signatures[1].block_id_flag = BlockIDFlag.NIL
    with pytest.raises(validation.ErrInvalidSignature):
        validation.verify_commit(CHAIN, vals, bid, 5, commit)
    # ...but light verification skips non-COMMIT sigs entirely and the
    # remaining 5/6 power still clears 2/3
    validation.verify_commit_light(CHAIN, vals, bid, 5, commit)


def test_verify_commit_size_mismatch():
    _, vals, bid, commit = _setup()
    commit.signatures.append(commit.signatures[0])
    with pytest.raises(validation.ErrInvalidCommitSize):
        validation.verify_commit(CHAIN, vals, bid, 5, commit)


def test_light_trusting_subset_overlap():
    # trusted set = 6 validators; commit from a 6-val set sharing 4 members
    signers_a = fx.make_signers(6, seed=11)
    vals_a = fx.make_validator_set(signers_a)
    signers_b = signers_a[:4] + fx.make_signers(2, seed=99)
    vals_b = fx.make_validator_set(signers_b)
    by_addr = {s.address(): s for s in signers_b}
    bid = fx.make_block_id(b"lc")
    commit = fx.make_commit(CHAIN, 9, 0, bid, vals_b, by_addr)
    # overlap power 40/60 > 1/3 of trusted set -> trusting check passes
    validation.verify_commit_light_trusting(CHAIN, vals_a, commit, (1, 3))
    # demanding >2/3 overlap: 40 > 40? no -> fails
    with pytest.raises(validation.ErrNotEnoughVotingPower):
        validation.verify_commit_light_trusting(CHAIN, vals_a, commit, (2, 3))


# ----------------------------------------------------------------------
# ISSUE 24: the span tree of one verify_commit
# ----------------------------------------------------------------------
def _traced_verify(tmp_path, n, light=False):
    import json
    import os

    from cometbft_tpu.utils import trace

    _, vals, bid, commit = _setup(n=n)
    sink = os.path.join(str(tmp_path), f"commit-{n}-{light}.jsonl")
    trace.configure(sink)
    try:
        fn = validation.verify_commit_light if light else validation.verify_commit
        fn(CHAIN, vals, bid, 5, commit)
        trace.flush()
        with open(sink, encoding="utf-8") as f:
            recs = [json.loads(line) for line in f]
    finally:
        trace.disable()
    # the tracer's own records come and go with the collector
    return [r for r in recs
            if r["name"] not in ("trace.clock", "trace.thread",
                                 "runtime.gc_pause")]


@pytest.mark.parametrize("light", [False, True])
def test_traced_verify_commit_is_one_tree_whatever_the_size(tmp_path, light):
    small = _traced_verify(tmp_path, 8, light)
    large = _traced_verify(tmp_path, 64, light)
    # per call, never per lane: the same records at 8 and at 64 validators
    assert [r["name"] for r in small] == [r["name"] for r in large]
    by = {r["name"]: r for r in large}
    assert set(by) == {
        "types.verify_commit", "types.commit_items",
        "types.verify_items_fill", "crypto.batch_verify",
        "crypto.materialize", "crypto.native_verify",
        "crypto.commit_partition"}
    root = by["types.verify_commit"]
    assert root["parent"] is None and root["height"] == 5
    assert {r["root"] for r in large} == {root["id"]}
    for name in ("types.commit_items", "types.verify_items_fill",
                 "crypto.batch_verify", "crypto.commit_partition"):
        assert by[name]["parent"] == root["id"], name
    for name in ("crypto.materialize", "crypto.native_verify"):
        assert by[name]["parent"] == by["crypto.batch_verify"]["id"], name
    # light stops once +2/3 is reached: 43 of 64 equal powers
    n = 43 if light else 64
    assert root["n"] == by["types.commit_items"]["n"] == n
    assert by["types.verify_items_fill"]["groups"] == 1
    assert by["types.verify_items_fill"]["singles"] == 0
    bv = by["crypto.batch_verify"]
    assert (bv["path"], bv["n"], bv["bucket"]) == ("native", n, 64)
    assert 0 <= by["types.commit_items"]["sign_bytes_ms"] \
        <= by["types.commit_items"]["dur_ms"]
    # the entry layer from inside: the root's self time is what its
    # direct children leave
    kids = sum(by[k]["dur_ms"] for k in (
        "types.commit_items", "types.verify_items_fill",
        "crypto.batch_verify"))
    # (less a collection's pause, should one fall straight under the root)
    assert 0 <= root["self_ms"] <= root["dur_ms"] - kids + 0.02
    assert bool(root.get("light")) == light


def test_untraced_verify_commit_emits_nothing(tmp_path):
    from cometbft_tpu.utils import trace

    assert not trace.enabled
    assert trace.span("types.verify_commit", height=1) is trace.span("x")
    _, vals, bid, commit = _setup()
    validation.verify_commit(CHAIN, vals, bid, 5, commit)
    assert trace.tail() == [] and trace.path() is None
