"""The plain reference of the configuration `ics-150v`: each commit judged
alone.

What the deployment promises is that a request's verdict and blame are those
its commit would get alone, whatever batch it rode in. So the reference knows
nothing of a scheduler, of a merge or of an engine: it takes ONE commit's lanes,
judges each with the pure-Python ZIP-215 verification beside this file, one
signature at a time, and turns the lane bitmap into verify_commit's answer for
a commit in which every validator signed with equal power: accepted, or
refused with blame on the lowest bad lane. It imports nothing of the program.
Slow by design (about 5 ms a signature).
"""

from __future__ import annotations

from . import ed25519_zip215 as ref


def lane_bitmap(lanes) -> list[bool]:
    """lanes: [(pubkey, sign bytes, signature)] of one commit, in slot order."""
    return [ref.verify(pub, msg, sig) for pub, msg, sig in lanes]


def answer(bits) -> int | None:
    """verify_commit's answer from one commit's lane bitmap: None where it is
    accepted, else the index that the refusal blames (the lowest bad lane)."""
    bad = [i for i, ok in enumerate(bits) if not ok]
    return min(bad) if bad else None


def judge(lanes) -> int | None:
    return answer(lane_bitmap(lanes))
