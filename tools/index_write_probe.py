#!/usr/bin/env python3
"""What one block's index batch costs on this host's file system, by what
sqlite is asked (ROADMAP B12): 400 records of 2.1 KB under random keys and
400 short sequential keys, one commit a block, 96 blocks a variant. No jax,
no chip needed; run it on the chip host through the chip tool, because the
hosts order the variants differently (PERF.md section 7).

    python tools/index_write_probe.py
"""
import hashlib, os, shutil, sqlite3, statistics, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from cometbft_tpu.storage.kv import SqliteKV

def batch(h):
    sets = []
    for i in range(400):
        tx = (b"a=" + hashlib.sha256(b"%d.%d" % (h, i)).hexdigest().encode() * 16)[:1024]
        k = hashlib.sha256(tx).digest()
        sets.append((b"TX:" + k, tx + tx[2:] + b"0123456789"))
        sets.append((b"tx.height/%d/%d/%d" % (h, h, i), k))
    return sets

def run(name, make, blocks=96):
    d = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", ".cache", "probe"); shutil.rmtree(d, ignore_errors=True); os.makedirs(d)
    write = make(os.path.join(d, "ix.db"))
    ts = []
    for h in range(1, blocks + 1):
        s = batch(h); t0 = time.perf_counter(); write(s); ts.append((time.perf_counter() - t0) * 1e3)
    size = sum(os.path.getsize(os.path.join(d, f)) for f in os.listdir(d)) / 1e6
    q = statistics.quantiles(ts, n=10)
    print(f"{name}: median {statistics.median(ts):.2f} ms, mean {statistics.fmean(ts):.2f}, p10 {q[0]:.2f}, p90 {q[-1]:.2f}, max {max(ts):.1f}; files {size:.1f} MB after {blocks} blocks", flush=True)
    shutil.rmtree(d, ignore_errors=True)

def as_is(path):
    return SqliteKV(path).write_batch

def pragma(*pragmas):
    def make(path):
        kv = SqliteKV(path)
        for p in pragmas: kv._conn.execute(p)
        return kv.write_batch
    return make

def without_rowid(path):
    c = sqlite3.connect(path, check_same_thread=False)
    c.execute("PRAGMA journal_mode=WAL"); c.execute("PRAGMA synchronous=NORMAL")
    c.execute("CREATE TABLE kv (k BLOB PRIMARY KEY, v BLOB NOT NULL) WITHOUT ROWID"); c.commit()
    def write(sets):
        c.executemany("INSERT INTO kv (k, v) VALUES (?, ?) ON CONFLICT(k) DO UPDATE SET v=excluded.v", sets); c.commit()
    return write

def seq_keys(path):
    w = SqliteKV(path).write_batch
    n = [0]
    def write(sets):
        out = []
        for k, v in sets:
            if k.startswith(b"TX:"):
                n[0] += 1; k = b"TX:" + n[0].to_bytes(8, "big")
            out.append((k, v))
        w(out)
    return write

print(sqlite3.sqlite_version)
run("as the program writes it (SqliteKV, WAL, NORMAL)", as_is)
run("the same, wal_autocheckpoint=0 (no checkpoint in the loop)", pragma("PRAGMA wal_autocheckpoint=0"))
run("the same, wal_autocheckpoint=16000", pragma("PRAGMA wal_autocheckpoint=16000"))
run("the same, cache_size=-262144 (256 MB page cache)", pragma("PRAGMA cache_size=-262144"))
run("WITHOUT ROWID (one b-tree)", without_rowid)
run("records under sequential keys (no random page)", seq_keys)
run("as the program writes it, again", as_is)
