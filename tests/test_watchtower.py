"""Watchtower auditor tests: every check pinned on an injected
adversary AND on clean worlds with zero false positives.

The adversarial fixtures are synthetic but real-crypto: forked feeds
are two +2/3 commits actually signed by the same validators, the
equivocation pairs carry verifying signatures, the certificate leg
runs a real BLS chain, and the DA leg serves real erasure-coded
chunks. The network-free `ingest_frame` / `handle_trace_record` /
`da_sweep` surface is the production code path minus the transport
threads, so what these tests pin is what the live auditor runs.
"""

import json
import os

import pytest

from cometbft_tpu.replication.feed import ReplicationFeed
from cometbft_tpu.types import (
    BlockID,
    BlockIDFlag,
    Commit,
    CommitSig,
    PartSetHeader,
    Timestamp,
)
from cometbft_tpu.types.agg_commit import AggregateCommit, CertCommit
from cometbft_tpu.types.evidence import decode_evidence
from cometbft_tpu.types.validator_set import Validator, ValidatorSet
from cometbft_tpu.types.vote import SignedMsgType, Vote
from cometbft_tpu.utils import factories as fx
from cometbft_tpu.utils.trace import TailReader
from cometbft_tpu.watchtower import Watchtower, checks

CHAIN = "wt-chain"
_CORES = os.cpu_count() or 1


@pytest.fixture(scope="module")
def world():
    store, state, genesis, signers = fx.make_chain(
        8, n_validators=4, chain_id=CHAIN)
    vals = fx.make_validator_set(signers)
    by_addr = {s.address(): s for s in signers}

    class _Vals:
        def load_validators(self, h):
            return vals

    feed = ReplicationFeed(CHAIN, store, _Vals())
    frames = [json.loads(feed._build_frame(store.load_block(h)))
              for h in range(1, 9)]
    return store, vals, by_addr, frames, signers


def _wt(names=("node0", "node1"), **kw):
    kw.setdefault("submit_evidence", False)
    return Watchtower({n: "" for n in names}, chain_id=CHAIN, **kw)


def _ingest_all(wt, frames, names):
    for frame in frames:
        for name in names:
            wt.ingest_frame(name, frame)


class _FakeClient:
    """broadcast_evidence sink shared across per-node instances."""

    calls: list = []

    def __init__(self, url):
        self.url = url

    def broadcast_evidence(self, evidence):
        _FakeClient.calls.append((self.url, evidence))
        return {"hash": "00"}


# ------------------------------------------------------------- clean
def test_clean_feeds_raise_nothing(world):
    _store, _vals, _by_addr, frames, _signers = world
    wt = _wt(("node0", "node1", "node2"))
    _ingest_all(wt, frames, ("node0", "node1", "node2"))
    assert wt.verdicts == []
    st = wt.status()
    assert all(n["audited"] == 8 for n in st["nodes"].values())
    ok, detail = wt.ready()
    assert ok and detail["verdicts"] == 0


def test_clean_20_seed_worlds_zero_false_positives():
    """The zero-FP pin the whole design leans on: 20 randomized clean
    worlds (different keys, proposer orders, tx mixes per seed) audited
    end to end must produce not a single verdict."""
    total = 0
    for seed in range(20):
        store, _state, _genesis, signers = fx.make_chain(
            4, n_validators=3, chain_id=f"clean-{seed}", seed=seed)
        vals = fx.make_validator_set(signers)

        class _Vals:
            def load_validators(self, h, _v=vals):
                return _v

        feed = ReplicationFeed(f"clean-{seed}", store, _Vals())
        frames = [json.loads(feed._build_frame(store.load_block(h)))
                  for h in range(1, 5)]
        wt = Watchtower({"a": "", "b": ""}, chain_id=f"clean-{seed}",
                        submit_evidence=False)
        _ingest_all(wt, frames, ("a", "b"))
        total += len(wt.verdicts)
        assert wt.verdicts == [], f"seed {seed}: {wt.verdicts}"
    assert total == 0


# -------------------------------------------------------------- fork
def test_fork_detected_and_culprits_named_exactly(world):
    _store, vals, by_addr, frames, _signers = world
    wt = _wt()
    _ingest_all(wt, frames[:-1], ("node0", "node1"))
    wt.ingest_frame("node0", frames[-1])
    # node1 reports a conflicting commit at the tip, signed by
    # validators 1..3 only (validator 0 absent): the culprit set is the
    # intersection of the two signer sets — exactly those three
    forked = fx.make_commit(
        CHAIN, 8, 0, fx.make_block_id(b"forked"), vals, by_addr,
        absent={0})
    f2 = dict(frames[-1])
    f2["seen"] = forked.encode().hex()
    wt.ingest_frame("node1", f2)
    forks = [v for v in wt.verdicts if v["check"] == "fork"]
    assert len(forks) == 1
    v = forks[0]
    assert v["safety"] is True and v["height"] == 8
    expect = sorted(val.address for i, val in enumerate(vals.validators)
                    if i != 0)
    assert v["culprits"] == [a.hex() for a in expect]
    # deduplicated on re-ingest
    wt.ingest_frame("node1", f2)
    assert len([x for x in wt.verdicts if x["check"] == "fork"]) == 1


# ------------------------------------------------------ equivocation
def test_cross_column_equivocation_builds_and_submits_evidence(world):
    _store, vals, by_addr, frames, _signers = world
    _FakeClient.calls = []
    wt = Watchtower({"node0": "http://a", "node1": "http://b"},
                    chain_id=CHAIN, client_factory=_FakeClient)
    wt.ingest_frame("node0", frames[-1])
    forked = fx.make_commit(
        CHAIN, 8, 0, fx.make_block_id(b"forked"), vals, by_addr,
        absent={0})
    f2 = dict(frames[-1])
    f2["seen"] = forked.encode().hex()
    wt.ingest_frame("node1", f2)
    evs = [v for v in wt.verdicts if v["check"] == "equivocation"]
    # validators 1..3 signed both columns at (8, 0) for different blocks
    assert len(evs) == 3
    assert all(v["safety"] for v in evs)
    named = {v["validator"] for v in evs}
    assert named == {val.address.hex()
                     for i, val in enumerate(vals.validators) if i != 0}
    # every evidence went to every watched node, and the wire form
    # decodes + verifies exactly as the receiving pool would check it
    assert len(_FakeClient.calls) == 6
    for _url, wire in _FakeClient.calls:
        ev = decode_evidence(bytes.fromhex(wire))
        ev.verify(CHAIN, vals)


def test_trace_record_equivocation_to_verified_evidence(world):
    _store, vals, _by_addr, frames, signers = world
    wt = _wt()
    wt.ingest_frame("node0", frames[2])  # vals for height 3
    s = signers[1]
    ts = Timestamp(1_700_000_000, 0)

    def vote(tag):
        v = Vote(type=SignedMsgType.PRECOMMIT, height=3, round=0,
                 block_id=fx.make_block_id(tag), timestamp=ts,
                 validator_address=s.address(), validator_index=1)
        fx.sign_vote(s, v, CHAIN)
        return v

    a, b = vote(b"one"), vote(b"two")
    rec = {"name": "consensus.conflicting_vote", "ts": 1.0,
           "vote_a": a.encode().hex(), "vote_b": b.encode().hex()}
    wt.handle_trace_record("node0", rec)
    evs = [v for v in wt.verdicts if v["check"] == "equivocation"]
    assert len(evs) == 1
    assert evs[0]["validator"] == s.address().hex()
    assert evs[0]["source"] == "trace:node0"
    # same pair again: deduplicated by evidence hash
    wt.handle_trace_record("node0", rec)
    assert len([v for v in wt.verdicts
                if v["check"] == "equivocation"]) == 1
    # a same-block "pair" is NOT equivocation and must not verdict
    rec2 = {"name": "consensus.conflicting_vote", "ts": 2.0,
            "vote_a": a.encode().hex(), "vote_b": a.encode().hex()}
    wt.handle_trace_record("node0", rec2)
    assert len([v for v in wt.verdicts
                if v["check"] == "equivocation"]) == 1


# ---------------------------------------------------------------- cert
def _bls_world(n_blocks=3, cert_native=True):
    from cometbft_tpu.abci.client import AppConns
    from cometbft_tpu.abci.kvstore import KVStoreApp
    from cometbft_tpu.crypto import bls
    from cometbft_tpu.state.execution import BlockExecutor, make_genesis_state
    from cometbft_tpu.storage import BlockStore, MemKV
    from cometbft_tpu.types.agg_commit import fold_commit
    from cometbft_tpu.types.block import block_id_for
    from cometbft_tpu.types.vote import canonical_vote_bytes

    chain_id = "wt-bls"
    keys = [bls.BlsPrivKey.from_secret(b"wt-bls-%d" % i) for i in range(4)]
    vals = ValidatorSet(
        [Validator.from_pub_key(k.pub_key(), 10) for k in keys])
    by_addr = {k.pub_key().address(): k for k in keys}
    store = BlockStore(MemKV())
    executor = BlockExecutor(AppConns(KVStoreApp()))
    state = make_genesis_state(chain_id, vals).copy()
    last_commit = Commit()
    for h in range(1, n_blocks + 1):
        proposer = state.validators.get_proposer()
        block = executor.create_proposal_block(
            h, state, last_commit, proposer.address, [b"k%d=v" % h],
            block_time=state.last_block_time)
        bid = block_id_for(block)
        vals_h = state.validators
        state = executor.apply_block(
            state, bid, block, last_commit_preverified=True)
        ts = Timestamp.from_unix_ns(
            state.last_block_time.unix_ns() + 1_000_000_000)
        msg = canonical_vote_bytes(
            SignedMsgType.PRECOMMIT, h, 0, bid, ts, chain_id)
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

    class _Vals:
        def load_validators(self, h):
            return vals

    feed = ReplicationFeed(chain_id, store, _Vals())
    frames = [json.loads(feed._build_frame(store.load_block(h)))
              for h in range(1, n_blocks + 1)]
    return chain_id, vals, frames


def test_cert_native_frames_verify_clean():
    chain_id, _vals, frames = _bls_world(cert_native=True)
    wt = Watchtower({"node0": ""}, chain_id=chain_id,
                    submit_evidence=False)
    for f in frames:
        assert f["cert"]["kind"] == "cert_native"
        wt.ingest_frame("node0", f)
    assert wt.verdicts == []


def test_cert_corrupt_aggregate_flagged():
    chain_id, _vals, frames = _bls_world(cert_native=True)
    wt = Watchtower({"node0": ""}, chain_id=chain_id,
                    submit_evidence=False)
    bad = dict(frames[-1])
    agg = AggregateCommit.decode(bytes.fromhex(bad["cert"]["data"]))
    sig = bytearray(agg.agg_sig)
    sig[0] ^= 0xFF  # corrupt only the aggregate signature
    agg.agg_sig = bytes(sig)
    bad["cert"] = {"kind": bad["cert"]["kind"], "data": agg.encode().hex()}
    wt.ingest_frame("node0", bad)
    certs = [v for v in wt.verdicts if v["check"] == "cert"]
    assert len(certs) >= 1
    assert certs[0]["safety"] is True and certs[0]["height"] == 3


def test_cert_column_mismatch_flagged_in_window():
    """The PR-17 seam audited externally: a bls_agg frame whose
    certificate claims a signer the retained column says was ABSENT."""
    chain_id, vals, frames = _bls_world(cert_native=False)
    wt = Watchtower({"node0": ""}, chain_id=chain_id,
                    submit_evidence=False, full_commit_window=16)
    for f in frames[:-1]:
        assert f["cert"]["kind"] == "bls_agg"
        wt.ingest_frame("node0", f)
    assert wt.verdicts == []
    bad = dict(frames[-1])
    seen = Commit.decode(bytes.fromhex(bad["seen"]))
    seen.signatures[2] = CommitSig.absent()
    seen.invalidate_memos()
    bad["seen"] = seen.encode().hex()
    wt.ingest_frame("node0", bad)
    certs = [v for v in wt.verdicts if v["check"] == "cert"]
    assert len(certs) == 1
    assert "signer 2" in certs[0]["detail"]
    assert "only in certificate" in certs[0]["detail"]


def test_cert_commit_matches_column_pure(world):
    _store, vals, by_addr, _frames, _signers = world
    column = fx.make_commit(
        CHAIN, 5, 0, fx.make_block_id(b"c"), vals, by_addr, absent={3})

    class _Cert:
        def has_signer(self, i):
            return i != 3

    cc = type("CC", (), {
        "height": 5, "round": 0,
        "block_id": fx.make_block_id(b"c"), "cert": _Cert()})()
    assert checks.cert_commit_matches_column(cc, column, vals) == []
    cc.height = 6
    assert any("height" in p for p in
               checks.cert_commit_matches_column(cc, column, vals))
    cc.height = 5
    cc.block_id = fx.make_block_id(b"other")
    probs = checks.cert_commit_matches_column(cc, column, vals)
    assert any("block id" in p for p in probs)


# ------------------------------------------------------------------ DA
def test_da_withholding_alarm_raises_and_clears(world):
    from cometbft_tpu.config import DAConfig
    from cometbft_tpu.da import DAServe

    store, vals, _by_addr, _frames, _signers = world
    srv = DAServe(DAConfig(enabled=True, data_shards=4, parity_shards=4))
    for h in range(1, 9):
        srv.on_commit(store.load_block(h))

    class _Vals:
        def load_validators(self, h):
            return vals

    feed = ReplicationFeed(CHAIN, store, _Vals(), da_serve=srv)
    frame = json.loads(feed._build_frame(store.load_block(8)))
    assert frame["da"]["root"]
    wt = Watchtower({"node0": ""}, chain_id=CHAIN, submit_evidence=False,
                    da_samples=4, da_alarm_after=2)
    wt.ingest_frame("node0", frame)

    withheld = lambda h, i: None  # noqa: E731 — everything withheld
    res = wt.da_sweep("node0", fetch=withheld)
    assert res.detected_withholding or res.samples_ok == 0
    assert [v for v in wt.verdicts if v["check"] == "da"] == []
    wt.da_sweep("node0", fetch=withheld)  # second consecutive bad sweep
    das = [v for v in wt.verdicts if v["check"] == "da"]
    assert len(das) == 1
    assert das[0]["safety"] is False  # alarm, not a safety violation
    assert das[0]["node"] == "node0" and das[0]["height"] == 8

    # honest serving clears the streak (a fresh sweep passes end to
    # end through real chunk + proof verification)
    res2 = wt.da_sweep("node0", fetch=lambda h, i: srv.sample(h, i))
    assert res2.samples_ok > 0 and not res2.detected_withholding
    assert wt._da_fail_streak["node0"] == 0
    assert len([v for v in wt.verdicts if v["check"] == "da"]) == 1
    srv.stop()


# --------------------------------------------------------------- stall
def test_online_stall_names_rejoining_node(tmp_path):
    from test_traceview import rejoin_stall_world

    _w, root = rejoin_stall_world(tmp_path)
    sinks = {n: os.path.join(root, n, "data", "trace.jsonl")
             for n in ("node0", "node1", "node2", "node3")}
    wt = Watchtower({n: "" for n in sinks}, chain_id=CHAIN,
                    submit_evidence=False, trace_sinks=sinks)
    for name, path in sinks.items():
        for rec in TailReader(path).poll():
            wt.handle_trace_record(name, rec)
    rep = wt.stall_pass()
    assert rep["status"] == "stall"
    stalls = [v for v in wt.verdicts if v["check"] == "stall"]
    assert len(stalls) == 1
    s = stalls[0]
    assert s["safety"] is False  # liveness, not safety
    assert s["node"] == "node3" and s["height"] == 5
    assert s["first_missing"] == "precommit"
    assert "catchup" in s["detail"]
    assert set(s["silent_peers"]) == {"node0", "node1", "node2"}
    # a second pass does not re-verdict the same stall
    wt.stall_pass()
    assert len([v for v in wt.verdicts if v["check"] == "stall"]) == 1


def test_online_stall_healthy_world_clean(tmp_path):
    from test_traceview import healthy_world

    _w, root = healthy_world(tmp_path)
    sinks = {n: os.path.join(root, n, "data", "trace.jsonl")
             for n in ("node0", "node1", "node2", "node3")}
    wt = Watchtower({n: "" for n in sinks}, chain_id=CHAIN,
                    submit_evidence=False, trace_sinks=sinks)
    for name, path in sinks.items():
        for rec in TailReader(path).poll():
            wt.handle_trace_record(name, rec)
    rep = wt.stall_pass()
    assert rep["status"] == "ok"
    assert wt.verdicts == []


# ---------------------------------------------------------- TailReader
def test_tail_reader_rotation_and_partial_lines(tmp_path):
    path = str(tmp_path / "sink.jsonl")
    r = TailReader(path)
    assert r.poll() == []  # missing file is not an error
    with open(path, "w") as f:
        f.write('{"a": 1}\n{"b": 2}\n')
    assert [x["a"] for x in r.poll() if "a" in x] == [1]
    # a partial line stays buffered until its newline arrives
    with open(path, "a") as f:
        f.write('{"c": ')
    assert r.poll() == []
    with open(path, "a") as f:
        f.write('3}\n')
    assert r.poll() == [{"c": 3}]
    # rotation: the file is replaced by a SHORTER one (logrotate /
    # trace.reset truncation); the reader must restart from zero
    # instead of seeking past EOF forever
    with open(path + ".new", "w") as f:
        f.write('{"d": 4}\n')
    os.replace(path + ".new", path)
    assert r.poll() == [{"d": 4}]
    # malformed lines are skipped, valid neighbours survive
    with open(path, "a") as f:
        f.write('not json\n{"e": 5}\n')
    assert r.poll() == [{"e": 5}]


# ------------------------------------------------------ byzantine valv
def test_byzantine_valv_equivocates_on_schedule(tmp_path):
    from cometbft_tpu.privval import FilePV
    from cometbft_tpu.privval.byzantine import (
        ByzantineValv, maybe_wrap, parse_schedule,
    )

    pv = FilePV.generate()
    bz = ByzantineValv(pv, parse_schedule(
        '[{"vote_type": "precommit", "from_height": 3, "to_height": 6}]'))
    vals = ValidatorSet([Validator.from_pub_key(pv.pub_key(), 10)])

    def vote(h, vtype=SignedMsgType.PRECOMMIT):
        v = Vote(type=vtype, height=h, round=0,
                 block_id=fx.make_block_id(b"real-%d" % h),
                 timestamp=Timestamp(1_700_000_000, 0),
                 validator_address=pv.address(), validator_index=0)
        bz.sign_vote(CHAIN, v)
        return v

    # FilePV's last-sign-state forbids HRS regression: sign the
    # out-of-scope votes in pipeline order before probing them
    v4_prevote = vote(4, SignedMsgType.PREVOTE)
    v4 = vote(4)
    shadow = bz.equivocate(CHAIN, v4)
    assert shadow is not None and bz.double_signed == 1
    assert shadow.height == 4 and shadow.round == 0
    assert shadow.type == SignedMsgType.PRECOMMIT
    assert shadow.block_id.key() != v4.block_id.key()
    # the shadow signature is REAL: it verifies under the pub key...
    assert pv.pub_key().verify_signature(
        shadow.sign_bytes(CHAIN), shadow.signature)
    # ...so the pair builds evidence any honest pool accepts
    ev = checks.build_duplicate_vote_evidence(v4, shadow, vals, CHAIN)
    assert ev is not None and ev.address() == pv.address()
    # out of window / wrong type / nil: no equivocation
    assert bz.equivocate(CHAIN, v4_prevote) is None
    assert bz.equivocate(CHAIN, vote(7)) is None
    nil = Vote(type=SignedMsgType.PRECOMMIT, height=4, round=0,
               block_id=BlockID(b"", PartSetHeader(0, b"")),
               timestamp=Timestamp(1_700_000_000, 0),
               validator_address=pv.address(), validator_index=0)
    assert bz.equivocate(CHAIN, nil) is None
    # env-var wrapping: absent -> untouched, present -> wrapped
    assert maybe_wrap(pv, env={}) is pv
    wrapped = maybe_wrap(pv, env={
        "COMETBFT_TPU_BYZANTINE": '[{"vote_type": "any"}]'})
    assert isinstance(wrapped, ByzantineValv)
    with pytest.raises(ValueError):
        parse_schedule('[{"vote_type": "sideways"}]')
    with pytest.raises(ValueError):
        parse_schedule('{"not": "a list"}')


# --------------------------------------------------------------- e2e
@pytest.mark.skipif(
    _CORES < 2,
    reason=f"subprocess net under an auditor starves the scheduler on a "
           f"single core (host has {_CORES})",
)
def test_e2e_byzantine_world_caught_and_evidence_committed(tmp_path):
    """The accountability loop end to end on a real net: node3
    double-signs precommits on schedule, the attached watchtower builds
    DuplicateVoteEvidence from the peers' conflicting-vote trace
    records and submits it over RPC, the pool gossips + commits it, and
    the run FAILS on the safety verdict."""
    from cometbft_tpu.e2e import Manifest, Runner
    from cometbft_tpu.e2e.runner import E2EError
    from cometbft_tpu.storage import BlockStore, open_kv

    m = Manifest.parse({
        "chain_id": "e2e-byz",
        "nodes": [{"name": f"node{i}"} for i in range(4)],
        "target_height": 10,
        "tx_rate": 5.0,
        "timeout_s": 150.0,
        "watchtower": True,
        "byzantine": [{"node": "node3", "vote_type": "precommit",
                       "from_height": 3, "to_height": 6}],
    })
    r = Runner(m, str(tmp_path))
    r.setup()
    assert "COMETBFT_TPU_BYZANTINE" in r.nodes["node3"].extra_env
    with pytest.raises(E2EError, match="safety verdict"):
        r.run()
    evs = [v for v in r.watchtower.verdicts
           if v["check"] == "equivocation"]
    assert evs, r.watchtower.verdicts
    # the culprit named is node3's validator
    import json as _json

    with open(os.path.join(str(tmp_path), "node3", "config",
                           "priv_validator_key.json")) as f:
        byz_addr = _json.load(f)["address"].lower()
    assert any(v["validator"] == byz_addr for v in evs)
    # ... and the evidence actually COMMITTED into a block somewhere
    committed = 0
    for i in range(4):
        bs = BlockStore(open_kv(os.path.join(
            str(tmp_path), f"node{i}", "data", "blockstore.db")))
        for h in range(1, bs.height() + 1):
            blk = bs.load_block(h)
            if blk is not None:
                committed += len(blk.evidence)
    assert committed > 0


@pytest.mark.skipif(
    _CORES < 2,
    reason=f"subprocess net under an auditor starves the scheduler on a "
           f"single core (host has {_CORES})",
)
def test_e2e_clean_world_audited_passes(tmp_path):
    from cometbft_tpu.e2e import Manifest, Runner

    m = Manifest.parse({
        "chain_id": "e2e-audited",
        "nodes": [{"name": f"node{i}"} for i in range(3)],
        "target_height": 6,
        "tx_rate": 5.0,
        "timeout_s": 120.0,
        "watchtower": True,
    })
    r = Runner(m, str(tmp_path))
    r.setup()
    r.run()  # raises on any safety verdict — clean world must not
    st = r.watchtower.status()
    assert st["safety_verdicts"] == 0
    assert all(n["audited"] >= 6 for n in st["nodes"].values())
    # the auditor opens its file at the first verdict: a clean world
    # leaves none, or an empty one
    p = os.path.join(str(tmp_path), "verdicts.jsonl")
    assert not os.path.exists(p) or os.path.getsize(p) == 0
