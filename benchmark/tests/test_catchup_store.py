"""The store the catch-up cell replays: written by the generator, settled,
closed and opened anew (drivers/catchup_replay.build_store, open_store). It
has to hold, block for block, what the generator run in-process on the same
seed holds, and hand over the same genesis state and final app hash."""

import os

from benchmark.drivers import catchup_replay as R

PARAMS = {"blocks": 16, "validators": 8, "txs_per_block": 2}  # the rehearsal's


def test_the_reopened_store_is_the_generators_block_for_block(tmp_path):
    seed = 2147483659
    a, b = str(tmp_path / "a.db"), str(tmp_path / "b.db")
    store_a, final, genesis, kv = R.make_store(
        a, PARAMS["blocks"], PARAMS["validators"], PARAMS["txs_per_block"], seed)
    R.build_store(b, PARAMS, seed)
    wal = b + "-wal"  # settled: nothing left in the log
    assert not os.path.exists(wal) or os.path.getsize(wal) == 0
    store_b, genesis_b, final_hash_b = R.open_store(b)
    try:
        assert store_b.height() == store_a.height() == PARAMS["blocks"]
        for h in range(1, PARAMS["blocks"] + 1):
            blk_a, blk_b = store_a.load_block(h), store_b.load_block(h)
            assert blk_b.hash() == blk_a.hash(), h
            assert blk_b.encode() == blk_a.encode(), h
            assert (store_b.load_seen_commit(h).encode()
                    == store_a.load_seen_commit(h).encode()), h
        assert final_hash_b == final.app_hash
        assert genesis_b.encode() == genesis.encode()
        assert genesis_b.validators.hash() == genesis.validators.hash()
    finally:
        kv.close()


def test_another_seed_is_another_chain(tmp_path):
    a, b = str(tmp_path / "a.db"), str(tmp_path / "b.db")
    R.build_store(a, PARAMS, 7)
    R.build_store(b, PARAMS, 8)
    (sa, _, ha), (sb, _, hb) = R.open_store(a), R.open_store(b)
    assert sa.load_block(2).hash() != sb.load_block(2).hash()
    assert os.path.getsize(a) > 0 and ha and hb
