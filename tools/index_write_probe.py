#!/usr/bin/env python3
"""What one block's index batch costs on this host's file system, by what
sqlite is asked (ROADMAP B12): 400 records of 2.1 KB under random keys and
400 short sequential keys, one commit a block. No jax, no chip needed; run
it on the chip host through the chip tool, because the hosts order the
variants differently (PERF.md section 7).

    python tools/index_write_probe.py [blocks]

Each variant is timed over `blocks` commits (256: the cell's 64 set-up
blocks and one pass; the times are of commits 65 on), then counted in a
second, untimed pass of 32 commits under `wal_autocheckpoint=0`: the
write-ahead log only grows there, so its size is the FRAMES a commit
leaves (one a dirty page: the layout fixes them, no host changes them).
`unused` is the share of the file's pages that holds nothing (`dbstat`,
where this sqlite has it). The last table in PERF.md comes from PR 43's
chip run (section 7, "chip host, PR 43").
"""
import hashlib, os, shutil, sqlite3, statistics, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from cometbft_tpu.storage.kv import SqliteKV

SETTLED = 64  # commits before the timed ones: the cell's aside replay
COUNTED = 32  # commits of the second, untimed pass

def batch(h):
    sets = []
    for i in range(400):
        tx = (b"a=" + hashlib.sha256(b"%d.%d" % (h, i)).hexdigest().encode() * 16)[:1024]
        k = hashlib.sha256(tx).digest()
        sets.append((b"TX:" + k, tx + tx[2:] + b"0123456789"))
        sets.append((b"tx.height/%d/%d/%d" % (h, h, i), k))
    return sets

def frames_and_unused(conn, path, write, first):
    """Frames a commit over COUNTED more commits with no checkpoint between
    them, and the file's unused share after them."""
    conn.execute("PRAGMA wal_autocheckpoint=0")
    conn.execute("PRAGMA wal_checkpoint(TRUNCATE)").fetchall()
    page = conn.execute("PRAGMA page_size").fetchone()[0]
    for h in range(first, first + COUNTED):
        write(batch(h))
    frames = (os.path.getsize(path + "-wal") - 32) / (24 + page) / COUNTED
    try:
        unused, held = conn.execute("SELECT sum(unused), sum(pgsize) FROM dbstat").fetchone()
        share = f"{100 * unused / held:.1f}%"
    except sqlite3.Error:
        share = "no dbstat"
    return page, frames, share

def run(name, make, blocks):
    d = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", ".cache", "probe"); shutil.rmtree(d, ignore_errors=True); os.makedirs(d)
    path = os.path.join(d, "ix.db")
    conn, write = make(path)
    ts = []
    for h in range(1, blocks + 1):
        s = batch(h); t0 = time.perf_counter(); write(s); ts.append((time.perf_counter() - t0) * 1e3)
    ts = ts[SETTLED:] if blocks > 2 * SETTLED else ts
    size = sum(os.path.getsize(os.path.join(d, f)) for f in os.listdir(d)) / 1e6
    page, frames, unused = frames_and_unused(conn, path, write, blocks + 1)
    q = statistics.quantiles(ts, n=10)
    print(f"{name}: median {statistics.median(ts):.2f} ms, mean {statistics.fmean(ts):.2f}, p10 {q[0]:.2f}, p90 {q[-1]:.2f}, max {max(ts):.1f} over {len(ts)} commits; "
          f"files {size:.1f} MB after {blocks} blocks; pages of {page}: {frames:.0f} frames a commit ({frames * (24 + page) / 1e6:.2f} MB), unused {unused}", flush=True)
    conn.close()
    shutil.rmtree(d, ignore_errors=True)

def as_is(path):
    kv = SqliteKV(path)
    return kv._conn, kv.write_batch

def page_size(n):
    def make(path):
        kv = SqliteKV(path, page_size=n)
        return kv._conn, kv.write_batch
    return make

def pragma(*pragmas):
    def make(path):
        kv = SqliteKV(path)
        for p in pragmas: kv._conn.execute(p)
        return kv._conn, kv.write_batch
    return make

def without_rowid(path):
    c = sqlite3.connect(path, check_same_thread=False)
    c.execute("PRAGMA journal_mode=WAL"); c.execute("PRAGMA synchronous=NORMAL")
    c.execute("CREATE TABLE kv (k BLOB PRIMARY KEY, v BLOB NOT NULL) WITHOUT ROWID"); c.commit()
    def write(sets):
        c.executemany("INSERT INTO kv (k, v) VALUES (?, ?) ON CONFLICT(k) DO UPDATE SET v=excluded.v", sets); c.commit()
    return c, write

def seq_keys(path):
    kv = SqliteKV(path)
    n = [0]
    def write(sets):
        out = []
        for k, v in sets:
            if k.startswith(b"TX:"):
                n[0] += 1; k = b"TX:" + n[0].to_bytes(8, "big")
            out.append((k, v))
        kv.write_batch(out)
    return kv._conn, write

if __name__ == "__main__":
    blocks = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    print(sqlite3.sqlite_version, f"{blocks} blocks a variant")
    run("as the program wrote it until PR 43 (SqliteKV, WAL, NORMAL, sqlite's page)", as_is, blocks)
    for n in (8192, 16384, 32768, 65536):
        run(f"the same, page_size {n}", page_size(n), blocks)
    run("as the program wrote it until PR 43, again", as_is, blocks)
    run("page_size 16384, again", page_size(16384), blocks)
    run("page_size 8192, again", page_size(8192), blocks)
    few = min(blocks, 96)  # PR 42's count: the first of these keeps its whole log
    run("sqlite's page, wal_autocheckpoint=0 (no checkpoint in the loop)", pragma("PRAGMA wal_autocheckpoint=0"), few)
    run("sqlite's page, wal_autocheckpoint=16000", pragma("PRAGMA wal_autocheckpoint=16000"), few)
    run("sqlite's page, cache_size=-262144 (256 MB page cache)", pragma("PRAGMA cache_size=-262144"), few)
    run("sqlite's page, records under sequential keys (no random page)", seq_keys, few)
    run("sqlite's page, WITHOUT ROWID (one b-tree)", without_rowid, few)
