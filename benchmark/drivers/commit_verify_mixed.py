"""Traffic kind `commit_verify_mixed`: `commit_verify`'s closed loop of one
caller through types/validation.verify_commit, over commits whose validators
hold keys of three curves (upstream's ConsensusParams.Validator.PubKeyTypes:
ed25519, sr25519, secp256k1).

Parameters (configuration shapes + the cell's traffic block): those of
commit_verify.py (commits, warmup_calls, profile_calls, device_from_lanes),
and from the configuration
  validators        signers of every commit, all three curves together
  sr25519           how many of them hold sr25519 keys
  secp256k1         how many hold secp256k1 keys; the rest hold ed25519 keys,
                    two of those the non-canonical ZIP-215 identity keys
The validators sit in the set's own order (voting power, then address), so
the curves interleave as the addresses fall.

Everything comes from --seed: the ed25519 scalars and nonces as in
commit_verify.py (utils/factories), the minority keys from seeded bytes, the
minority signatures from the program's own Python signers (schnorrkel's
witness randomness drawn from the seed, RFC 6979 for ECDSA).

`correct` (every limit 0, each number printed beside its limit), besides
commit_verify.py's "no timed call refused an honest commit":
  on a seeded commit of the K, honest and in a corrupted copy with bad lanes
  on ALL THREE curves (ed25519: flipped R, flipped S, S >= L, a wrong
  signature under a non-canonical key; sr25519: a flipped byte, the
  non-canonical negative twin of R; secp256k1: a flipped byte, the upper-S
  twin, which plain ECDSA accepts),
  - the ed25519 lanes are judged as commit_verify.py judges them
    (check.bitmap_checks: generator, OpenSSL, host engine, ZIP-215 reference);
  - every sr25519 and secp256k1 lane's verdict from the verifiers the timed
    path calls (Sr25519BatchVerifier, secp256k1.verify_many) equals the
    generator's knowledge, on all of them; OpenSSL's ECDSA with the low-S
    rule on every secp256k1 lane; and the plain references
    (benchmark/reference/sr25519_schnorrkel.py, secp256k1_ecdsa.py) on the
    broken lanes and 16 seeded lanes of each curve;
  - verify_commit refuses the corrupted copy with blame on the LOWEST bad
    index of the commit, whichever curve it lies on;
  a second copy whose only bad lane is a minority lane in the upper half of
  the commit is refused with blame exactly there.
The path checks are the harness's (device_from_lanes: the ed25519 batch on a
device path, none on a host path, nothing compiled in the window).

A program that cannot run the configuration gets no result line. The
configuration's last guarantee (blame on the LOWEST bad index, whichever
curve it lies on) is the one a program that judges its curves one after the
other does not give, and on such a program the blame comparison above passes
or fails by where a seed's bad lanes fall. So set-up begins by asking for it
(blame_probe: a dozen validators of the three curves, one bad lane on each,
the lowest on each curve in turn; pure Python, the chip not touched), and a
program that blames another index ends the run there, with an error and
exit code 1, before any data is built. The probe asks for the order alone: a
program that lets a bad lane pass (the accept_all control) gets its line,
with `correct` false.
"""

from __future__ import annotations

import contextlib
import copy
import time
import types

from benchmark.drivers import commit_verify as base
from benchmark.harness import check as C
from benchmark.harness.env import log

CHAIN = base.CHAIN
MINORITY = ("sr25519", "secp256k1")
SAMPLED_LANES = 16


def curve_of(pub_key) -> str:
    """"tendermint/PubKeySr25519" -> "sr25519"."""
    return pub_key.type_tag().rsplit("PubKey", 1)[-1].lower()


# ---------------------------------------------------------------------
# the comparisons (fed by hand in benchmark/tests/test_checks_mixed.py)


def blame_check(name: str, blamed: str, index: int) -> C.Check:
    """verify_commit's answer to a bad commit against the index it has to
    blame; `blamed` is the error's text, or "accepted"."""
    return C.Check(name, blamed, f"refused with 'index {index}'",
                   blamed.endswith(f"index {index}"))


def openssl_ecdsa_low_s(lanes) -> list[bool]:
    """OpenSSL's ECDSA over secp256k1 on every lane, and upstream's low-S
    rule on top (OpenSSL itself accepts both twins)."""
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric import ec, utils

    from benchmark.reference import secp256k1_ecdsa as ref

    out = []
    for pub, msg, sig in lanes:
        r = int.from_bytes(sig[:32], "big")
        s = int.from_bytes(sig[32:], "big")
        try:
            key = ec.EllipticCurvePublicKey.from_encoded_point(
                ec.SECP256K1(), pub)
            key.verify(utils.encode_dss_signature(r, s), msg,
                       ec.ECDSA(hashes.SHA256()))
            out.append(s <= ref.N // 2)
        except (InvalidSignature, ValueError):
            out.append(False)
    return out


def reference_verify(curve: str):
    from benchmark.reference import secp256k1_ecdsa, sr25519_schnorrkel

    return {"sr25519": sr25519_schnorrkel.verify,
            "secp256k1": secp256k1_ecdsa.verify}[curve]


def minority_checks(tag: str, curve: str, bits, lanes, bad: set,
                    seed: int) -> list[C.Check]:
    """Lanes of one minority curve on which the program's verdicts `bits`
    differ from each judge; every limit is 0. `bad` holds the positions in
    `lanes` that the generator broke."""
    import numpy as np

    n = len(lanes)
    name = f"{tag}.{curve}"
    out = [C.equal(f"{name}.lanes_judged", len(bits), n)]
    if len(bits) != n:
        return out
    out.append(C.equal(f"{name}.lanes_differing_from_generator",
                       sum(b != (i not in bad) for i, b in enumerate(bits)),
                       0))
    if curve == "secp256k1":
        ossl = openssl_ecdsa_low_s(lanes)
        out.append(C.equal(
            f"{name}.lanes_differing_from_openssl_low_s_of_{n}",
            sum(b != o for b, o in zip(bits, ossl)), 0))
    rng = np.random.default_rng([seed, 4, MINORITY.index(curve)])
    sample = sorted(set(bad) | set(
        rng.choice(n, size=min(SAMPLED_LANES, n), replace=False).tolist()))
    ref = reference_verify(curve)
    out.append(C.equal(
        f"{name}.lanes_differing_from_plain_reference_of_{len(sample)}",
        sum(ref(*lanes[i]) != bits[i] for i in sample), 0))
    return out


def program_minority_bits(curve: str, lanes) -> list[bool]:
    """Per-lane verdicts from the verifier verify_commit hands this curve's
    partition to."""
    from cometbft_tpu.crypto import secp256k1 as K1
    from cometbft_tpu.crypto.sr25519 import Sr25519BatchVerifier, Sr25519PubKey

    if curve == "secp256k1":
        return list(K1.verify_many(lanes))
    bv = Sr25519BatchVerifier()
    took = [bv.add(Sr25519PubKey(pub), msg, sig) for pub, msg, sig in lanes]
    bits = iter(bv.verify()[1])
    return [t and next(bits) for t in took]


# ---------------------------------------------------------------------
# the corruptions


def _mutate(commit, idx: int, fn) -> None:
    sig = bytearray(commit.signatures[idx].signature)
    fn(sig)
    commit.signatures[idx].signature = bytes(sig)


def _flip(at: int, mask: int):
    def fn(sig):
        sig[at] ^= mask
    return fn


def _ed_s_plus_l(sig) -> None:
    from benchmark.reference import ed25519_zip215 as ref

    s = int.from_bytes(sig[32:], "little") + ref.L
    sig[32:] = s.to_bytes(32, "little")


def _sr_negative_r(sig) -> None:
    """R's negative twin p - s: the same point to a decoder that skips the
    sign check, an encoding RFC 9496 refuses (s must be even)."""
    from benchmark.reference import sr25519_schnorrkel as ref

    s = int.from_bytes(sig[:32], "little")
    sig[:32] = ((ref.P - s) % ref.P).to_bytes(32, "little")


def _secp_upper_s(sig) -> None:
    from benchmark.reference import secp256k1_ecdsa as ref

    s = int.from_bytes(sig[32:], "big")
    sig[32:] = (ref.N - s).to_bytes(32, "big")


def corrupt_three_curves(commit, by_curve: dict, weird: list[int], seed: int):
    """A copy with bad lanes on all three curves at seeded places;
    (commit, {index: why})."""
    import numpy as np

    rng = np.random.default_rng([seed, 5])
    bad = copy.deepcopy(commit)
    why = {}

    def pick(curve: str, k: int) -> list[int]:
        free = [i for i in by_curve[curve] if i not in weird]
        return [free[j] for j in rng.permutation(len(free))[:k].tolist()]

    def garbage(sig):
        sig[:] = rng.bytes(32) + (1).to_bytes(32, "little")

    e = pick("ed25519", 3)
    sr = pick("sr25519", 2)
    k1 = pick("secp256k1", 2)
    for idx, fn, label in (
            (e[0], _flip(3, 0x10), "ed25519: flipped bit in R"),
            (e[1], _flip(40, 0x01), "ed25519: flipped bit in S"),
            (e[2], _ed_s_plus_l, "ed25519: S >= L"),
            (weird[1], garbage, "ed25519: non-canonical A, wrong signature"),
            (sr[0], _flip(7, 0x01), "sr25519: flipped bit in R"),
            (sr[1], _sr_negative_r, "sr25519: non-canonical R (negative twin)"),
            (k1[0], _flip(7, 0x01), "secp256k1: flipped bit in r"),
            (k1[1], _secp_upper_s, "secp256k1: upper-S twin")):
        _mutate(bad, idx, fn)
        why[idx] = label
    bad.invalidate_memos()
    return bad, why


def corrupt_one_minority_lane(commit, by_curve: dict, seed: int):
    """A copy whose only bad lane is a minority lane in the upper half of
    the commit; (commit, index)."""
    import numpy as np

    n = len(commit.signatures)
    minority = [i for c in MINORITY for i in by_curve[c]]
    # (a rehearsal's few minority lanes can all fall in the lower half)
    upper = [i for i in minority if i >= n // 2] or [max(minority)]
    idx = upper[int(np.random.default_rng([seed, 6]).integers(len(upper)))]
    bad = copy.deepcopy(commit)
    _mutate(bad, idx, _flip(40, 0x01))
    bad.invalidate_memos()
    return bad, idx


# ---------------------------------------------------------------------


class _Signer:
    """A minority-curve validator: the program's private key object."""

    def __init__(self, priv):
        self.priv = priv
        self._pub = priv.pub_key()

    def pub_key(self):
        return self._pub

    def address(self) -> bytes:
        return self._pub.address()


def _place(signers):
    """(validator set, signers in the set's order, {curve: indices})."""
    from cometbft_tpu.utils import factories as fx

    vals = fx.make_validator_set(signers)
    by_addr = {s.address(): s for s in signers}
    order = [by_addr[v.address] for v in vals.validators]
    by_curve = {c: [] for c in ("ed25519",) + MINORITY}
    for i, v in enumerate(vals.validators):
        by_curve[curve_of(v.pub_key)].append(i)
    return vals, order, by_curve


def _minority_signers(rng, n_sr: int, n_k1: int) -> list:
    from cometbft_tpu.crypto.secp256k1 import Secp256k1PrivKey
    from cometbft_tpu.crypto.sr25519 import Sr25519PrivKey

    return ([_Signer(Sr25519PrivKey(rng.bytes(32))) for _ in range(n_sr)]
            + [_Signer(Secp256k1PrivKey.from_secret(rng.bytes(32)))
               for _ in range(n_k1)])


@contextlib.contextmanager
def _witness_from(rng):
    """schnorrkel's signer mixes fresh randomness into its witness; inside,
    it comes from `rng`, so that a seed gives the same commits."""
    from cometbft_tpu.crypto import sr25519 as SR

    real = SR.secrets
    SR.secrets = types.SimpleNamespace(token_bytes=rng.bytes)
    try:
        yield
    finally:
        SR.secrets = real


def _signed_commit(vals, order, by_curve, height: int, bid, ed_sign):
    """(commit of every validator, seconds the minority signatures took);
    `ed_sign(signers, msgs)` gives the ed25519 signatures."""
    from cometbft_tpu.types import Commit, CommitSig, Timestamp
    from cometbft_tpu.types.block import BlockIDFlag

    commit = Commit(height=height, round=0, block_id=bid, signatures=[
        CommitSig(BlockIDFlag.COMMIT, v.address,
                  Timestamp.from_unix_ns(1_700_000_000_000_000_000 + i), b"")
        for i, v in enumerate(vals.validators)])
    msgs = [commit.vote_sign_bytes(CHAIN, i) for i in range(len(order))]
    ed = by_curve["ed25519"]
    for i, sig in zip(ed, ed_sign([order[i] for i in ed],
                                  [msgs[i] for i in ed])):
        commit.signatures[i].signature = sig
    t0 = time.perf_counter()
    for c in MINORITY:
        for i in by_curve[c]:
            commit.signatures[i].signature = order[i].priv.sign(msgs[i])
    took = time.perf_counter() - t0
    commit.invalidate_memos()
    return commit, took


def _refusal(vals, bid, height: int, commit) -> str:
    """verify_commit's answer: the error's text, or "accepted"."""
    from cometbft_tpu.types import validation

    try:
        validation.verify_commit(CHAIN, vals, bid, height, commit)
        return "accepted"
    except validation.ErrInvalidSignature as e:
        return str(e)


PROBE = {"ed25519": 6, "sr25519": 3, "secp256k1": 3}


def blame_probe(seed: int) -> list[C.Check]:
    """Does the program give the configuration's blame guarantee at all?
    One small commit of the three curves, and three copies with one bad lane
    on EACH curve: the first lane of one curve and the last of the two
    others, each curve first in turn. The probe asks for the ORDER of blame
    alone: a lane that the program does not refuse when it is the only bad
    one is left out of the order (that is a wrong verdict, which the
    comparisons after the window report in a result line). Keys and
    signatures in pure Python (the ed25519 keys by the plain reference), so
    nothing here touches the device or compiles."""
    import numpy as np

    from benchmark.reference import ed25519_zip215 as ref
    from cometbft_tpu.utils import factories as fx

    rng = np.random.default_rng([seed, 8])
    while True:
        signers = []
        for _ in range(PROBE["ed25519"]):
            a = int.from_bytes(rng.bytes(32), "little") % ref.L or 1
            pub = ref._encode_point(*ref._ext_to_affine(
                ref._ext_scalar_mul(a, ref.B_POINT)))
            signers.append(fx.ScalarSigner(a, pub))
        signers += _minority_signers(rng, PROBE["sr25519"],
                                     PROBE["secp256k1"])
        vals, order, by_curve = _place(signers)
        # (one draw in 924 puts every ed25519 lane below every other, and
        # then no minority lane can be the lowest of the three)
        if by_curve["ed25519"][-1] > min(by_curve[c][0] for c in MINORITY):
            break
    bid = fx.make_block_id(b"bench-probe-%d" % seed)
    with _witness_from(rng):
        commit, _ = _signed_commit(
            vals, order, by_curve, 1, bid,
            lambda who, msgs: [fx.sign_with_scalar(s, m)
                               for s, m in zip(who, msgs)])

    def refusal(lanes) -> str:
        bad = copy.deepcopy(commit)
        for i in lanes:
            _mutate(bad, i, _flip(40, 0x01))
        bad.invalidate_memos()
        return _refusal(vals, bid, 1, bad)

    ends = sorted({i for idxs in by_curve.values()
                   for i in (idxs[0], idxs[-1])})
    refused_alone = {i for i in ends
                     if refusal([i]).endswith(f"index {i}")}
    out = []
    for first in by_curve:
        lanes = sorted({idxs[0] if c == first else idxs[-1]
                        for c, idxs in by_curve.items()} & refused_alone)
        if len(lanes) > 1:
            out.append(blame_check(
                f"probe.bad_lanes_at_{'_'.join(map(str, lanes))}"
                f".verify_commit", refusal(lanes), lanes[0]))
    return out


class Driver(base.Driver):
    def setup(self) -> None:
        t0 = time.perf_counter()
        probe = blame_probe(self.ctx.seed)
        if not all(c.ok for c in probe):
            for c in probe:
                c.show()
            raise SystemExit(
                "FAIL: the program does not give this configuration's "
                "guarantee (a commit with bad lanes on several curves is "
                "refused with blame on the LOWEST bad index): it cannot run "
                "this configuration")
        log(f"   blame probe: {len(probe)} answers as the configuration "
            f"guarantees, {time.perf_counter() - t0:.2f}s")
        super().setup()

    def _build(self):
        import numpy as np

        from cometbft_tpu.types import Commit
        from cometbft_tpu.utils import factories as fx

        p, seed = self.p, self.ctx.seed
        n, k = p["validators"], p["commits"]
        n_sr, n_k1 = p["sr25519"], p["secp256k1"]
        n_ed = n - n_sr - n_k1
        rng = np.random.default_rng([seed, 7])
        t0 = time.perf_counter()
        signers = fx.make_signers(n_ed - 2, seed=seed)
        signers += [fx.ScalarSigner(0, enc)
                    for enc in C.noncanonical_identity_keys()]
        signers += _minority_signers(rng, n_sr, n_k1)
        self.vals, order, self.by_curve = _place(signers)
        self.weird = [i for i in self.by_curve["ed25519"]
                      if order[i].scalar == 0]
        t_keys = time.perf_counter() - t0
        self.bids, self.encoded = [], []
        t_minority = 0.0
        with _witness_from(rng):
            for h in range(1, k + 1):
                bid = fx.make_block_id(b"bench-%d-%d" % (seed, h))
                commit, took = _signed_commit(
                    self.vals, order, self.by_curve, h, bid,
                    lambda who, msgs, h=h: fx.batch_sign(
                        who, msgs, seed=seed * 1000 + h))
                t_minority += took
                self.bids.append(bid)
                self.encoded.append(commit.encode())
        self.decode = Commit.decode
        self.ctx.objects_tracked("data built")
        log(f"   built {n} validators ({n_ed} ed25519 with non-canonical "
            f"keys at {self.weird}, {n_sr} sr25519, {n_k1} secp256k1; keys "
            f"{t_keys:.1f}s) and {k} commits of {len(self.encoded[0])} bytes "
            f"in {time.perf_counter() - t0:.1f}s, of it "
            f"{k * (n_sr + n_k1)} minority signatures {t_minority:.1f}s")

    # -- checks --------------------------------------------------------

    def _refusal(self, k: int, commit) -> str:
        return _refusal(self.vals, self.bids[k], k + 1, commit)

    def _judge(self, tag: str, commit, why: dict) -> list:
        """Every lane of one commit, each curve by its own judges."""
        seed = self.ctx.seed
        lanes = C.commit_lanes(CHAIN, self.vals, commit)
        ed = self.by_curve["ed25519"]
        pos = {i: j for j, i in enumerate(ed)}
        ed_lanes = [lanes[i] for i in ed]
        ok, bits = C.program_bitmap(ed_lanes)
        ed_why = {pos[i]: w for i, w in why.items() if i in pos}
        out = [C.equal(f"{tag}.ed25519.batch_ok", ok, not ed_why)]
        out += C.bitmap_checks(f"{tag}.ed25519", bits, ed_lanes, ed_why,
                               [pos[i] for i in self.weird], seed)
        for curve in MINORITY:
            idxs = self.by_curve[curve]
            mine = [lanes[i] for i in idxs]
            bad = {j for j, i in enumerate(idxs) if i in why}
            out += minority_checks(tag, curve,
                                   program_minority_bits(curve, mine), mine,
                                   bad, seed)
        return out

    def verify(self) -> list:
        """Outside the window, on a seeded commit of the K."""
        import numpy as np

        seed = self.ctx.seed
        k = int(np.random.default_rng([seed, 1]).integers(len(self.encoded)))
        out = [C.equal("timed_calls_refused", self.failed, 0),
               C.at_least("timed_calls", len(self.samples), 1)]
        commit = self.decode(self.encoded[k])
        out += self._judge("honest", commit, {})

        bad, why = corrupt_three_curves(commit, self.by_curve, self.weird,
                                        seed)
        log(f"   corrupted commit {k + 1}: "
            f"{ {i: why[i] for i in sorted(why)} }")
        out.append(blame_check("corrupted.verify_commit",
                               self._refusal(k, bad), min(why)))
        out += self._judge("corrupted", bad, why)

        one, idx = corrupt_one_minority_lane(commit, self.by_curve, seed)
        log(f"   second copy: one bad lane at {idx} "
            f"({curve_of(self.vals.validators[idx].pub_key)})")
        out.append(blame_check("one_minority_lane.verify_commit",
                               self._refusal(k, one), idx))
        return out
