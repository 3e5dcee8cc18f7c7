"""What decides `correct`. Every number compared is printed beside its limit.

The comparisons of the commit cells are copies of chip_smoke.py's (PR 21, ran
on the chip): a corrupted copy of a commit must be refused with blame on its
first bad index, and the lane bitmap of the program's batch verifier must be
the one three independent judges give: the generator's own knowledge of which
lanes it broke, OpenSSL's Ed25519 on every lane with a canonical key, and the
benchmark's pure-Python ZIP-215 reference (benchmark/reference) on the broken
lanes, the non-canonical lanes and 16 seeded lanes. The program's host C++
engine is asked too, lane by lane, as chip_smoke did.
"""

from __future__ import annotations

import copy
from dataclasses import dataclass

from .env import log


@dataclass
class Check:
    name: str
    value: object
    limit: object
    ok: bool

    def show(self) -> None:
        log(f"   check {self.name}: {self.value} (limit {self.limit}) "
            f"{'ok' if self.ok else 'FAIL'}")


def equal(name: str, value, want) -> Check:
    return Check(name, value, f"== {want}", value == want)


def at_least(name: str, value, limit) -> Check:
    return Check(name, value, f">= {limit}", value >= limit)


# ---------------------------------------------------------------------
# commits


def noncanonical_identity_keys() -> list[bytes]:
    """Two non-canonical encodings of the identity that ZIP-215 accepts:
    y = p + 1 (>= p), and the same with the sign bit set on x = 0."""
    y = (2**255 - 19) + 1
    e0 = y.to_bytes(32, "little")
    e1 = bytearray(e0)
    e1[31] |= 0x80
    return [e0, bytes(e1)]


def corrupt_commit(commit, weird: list[int], seed: int):
    """A copy with four bad lanes at seeded places; (commit, {idx: why})."""
    import numpy as np

    from benchmark.reference import ed25519_zip215 as ref

    n = len(commit.signatures)
    rng = np.random.default_rng([seed, 2])
    free = [i for i in rng.permutation(n).tolist() if i not in weird]
    bad = copy.deepcopy(commit)
    why = {}

    def mutate(idx, fn, label):
        sig = bytearray(bad.signatures[idx].signature)
        fn(sig)
        bad.signatures[idx].signature = bytes(sig)
        why[idx] = label

    def flip_r(sig):
        sig[3] ^= 0x10

    def flip_s(sig):
        sig[40] ^= 0x01

    def s_plus_l(sig):
        s = int.from_bytes(sig[32:], "little") + ref.L
        sig[32:] = s.to_bytes(32, "little")

    def garbage(sig):
        sig[:] = rng.bytes(32) + (1).to_bytes(32, "little")

    mutate(free[0], flip_r, "flipped bit in R")
    mutate(free[1], flip_s, "flipped bit in S")
    mutate(free[2], s_plus_l, "S >= L")
    # of the two non-canonical keys one keeps its valid signature (every
    # engine must ACCEPT it), the other gets a wrong one
    mutate(weird[1], garbage, "non-canonical A, wrong signature")
    bad.invalidate_memos()
    return bad, why


def commit_lanes(chain: str, vals, commit):
    return [(vals.validators[i].pub_key.bytes(),
             commit.vote_sign_bytes(chain, i), cs.signature)
            for i, cs in enumerate(commit.signatures)]


def program_bitmap(lanes, **kw):
    """(ok, bits) of the program's batch verifier over these lanes, through
    its normal dispatch."""
    from cometbft_tpu.crypto.ed25519 import Ed25519BatchVerifier, Ed25519PubKey

    bv = Ed25519BatchVerifier(backend="tpu", **kw)
    for pub, msg, sig in lanes:
        bv.add(Ed25519PubKey(pub), msg, sig)
    return bv.verify()


def _openssl_verdicts(lanes, skip: set) -> dict[int, bool]:
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PublicKey,
    )

    out = {}
    keys: dict[bytes, object] = {}
    for i, (pub, msg, sig) in enumerate(lanes):
        if i in skip:
            continue
        key = keys.get(pub)
        if key is None:
            key = keys[pub] = Ed25519PublicKey.from_public_bytes(pub)
        try:
            key.verify(sig, msg)
            out[i] = True
        except InvalidSignature:
            out[i] = False
    return out


def bitmap_checks(tag: str, bits, lanes, why: dict, weird: list[int],
                  seed: int) -> list[Check]:
    """Lanes on which `bits` differs from each judge; every limit is 0."""
    import numpy as np

    from benchmark.reference import ed25519_zip215 as ref
    from cometbft_tpu.crypto import native

    n = len(lanes)
    expect = [i not in why for i in range(n)]
    out = [equal(f"{tag}.lanes_judged", len(bits), n)]
    if len(bits) != n:
        return out
    out.append(equal(f"{tag}.lanes_differing_from_generator",
                     sum(b != e for b, e in zip(bits, expect)), 0))
    ossl = _openssl_verdicts(lanes, set(weird))
    out.append(equal(f"{tag}.lanes_differing_from_openssl_of_{len(ossl)}",
                     sum(bits[i] != v for i, v in ossl.items()), 0))
    host = [native.verify(p, m, s) for p, m, s in lanes]
    out.append(equal(f"{tag}.lanes_differing_from_host_engine",
                     sum(b != h for b, h in zip(bits, host)), 0))
    rng = np.random.default_rng([seed, 3])
    sample = sorted(set(why) | set(weird) | set(
        rng.choice(n, size=min(16, n), replace=False).tolist()))
    out.append(equal(
        f"{tag}.lanes_differing_from_zip215_reference_of_{len(sample)}",
        sum(ref.verify(*lanes[i]) != bits[i] for i in sample), 0))
    return out


# ---------------------------------------------------------------------
# every cell


def path_checks(delta: dict, spans: list | None, device_from_lanes,
                expected_batches: int | None) -> list[Check]:
    """Every batch of device_from_lanes lanes or more took a device path.
    From the counter always; from the program's spans too when tracing is on.
    device_from_lanes None: the cell's batches are all below the line, and
    nothing is asked."""
    from .env import DEVICE_PATHS, dispatch_counts

    out = []
    if device_from_lanes is None:
        return out
    dev, host = dispatch_counts(delta)
    out.append(equal("batches_on_a_host_path", int(host), 0))
    if expected_batches is not None:
        out.append(at_least("batches_on_a_device_path", int(dev),
                            expected_batches))
    if spans is not None:
        hidden = [(int(r["n"]), r["path"]) for r in spans
                  if r.get("name") == "crypto.batch_verify" and "path" in r
                  and int(r["n"]) >= device_from_lanes
                  and r["path"] not in DEVICE_PATHS]
        out.append(equal("spans_of_big_batches_on_a_host_path",
                         len(hidden), 0))
    gave = {k[0]: v for k, v in delta["gave_way_total"].items()}
    out.append(equal("lanes_sent_to_the_host_at_result",
                     int(gave.get("oversize", 0)), 0))
    return out


def compile_checks(watch, t0: float, t1: float) -> list[Check]:
    inside = watch.between(t0, t1)
    ver = [e for e in inside if watch.is_verify(e["fn"])]
    for e in inside:
        log(f"   compile inside the window: {e['fn']} {e['s']:.2f}s "
            f"{'cache hit' if e['cache_hit'] else 'compiled'}")
    return [equal("verify_programs_compiled_or_loaded_in_window",
                  len(ver), 0)]
