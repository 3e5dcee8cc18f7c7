"""The page size a SqliteKV's file is made with (storage/kv.py): given, it
is the connection's first statement and fixes a NEW file's pages; a file
that exists keeps its own; without one the store opens as it always did."""

import os
import sqlite3

import pytest

from cometbft_tpu.storage import MemKV, open_kv
from cometbft_tpu.storage import kv as kvmod
from cometbft_tpu.storage.kv import SqliteKV

SQLITE_DEFAULT = 4096  # sqlite's own page since 3.12


def _pragma(path: str, name: str):
    """What the FILE says, from a connection of its own."""
    conn = sqlite3.connect(path)
    try:
        return conn.execute(f"PRAGMA {name}").fetchone()[0]
    finally:
        conn.close()


@pytest.mark.parametrize("asked, has", [
    (None, SQLITE_DEFAULT), (4096, 4096), (8192, 8192), (16384, 16384),
    (65536, 65536)])
@pytest.mark.parametrize("opener", (SqliteKV, open_kv))
def test_a_new_file_has_the_pages_it_was_asked_for(tmp_path, opener, asked,
                                                   has):
    path = str(tmp_path / "s.db")
    kv = opener(path) if asked is None else opener(path, page_size=asked)
    kv.write_batch([(b"k%d" % i, b"v" * 3000) for i in range(50)])
    assert kv.page_bytes == has
    assert kv.get(b"k7") == b"v" * 3000
    kv.close()
    assert _pragma(path, "page_size") == has
    assert _pragma(path, "journal_mode") == "wal"


@pytest.mark.parametrize("made, asked", [
    (None, 16384), (16384, None), (8192, 65536), (16384, 4096)])
def test_a_file_that_exists_keeps_its_pages(tmp_path, made, asked):
    path = str(tmp_path / "s.db")
    kv = SqliteKV(path, made)
    had = kv.page_bytes
    assert had == (made or SQLITE_DEFAULT)
    before = [(b"old%03d" % i, os.urandom(2100)) for i in range(40)]
    kv.write_batch(before)
    kv.close()
    kv = open_kv(path, asked)
    assert kv.page_bytes == had
    assert [(k, v) for k, v in kv.iterate_prefix(b"old")] == before
    kv.write_batch([(b"new%03d" % i, b"n" * 2100) for i in range(40)],
                   deletes=[before[0][0]])
    assert kv.get(b"new039") == b"n" * 2100
    assert kv.get(before[0][0]) is None and kv.get(before[1][0]) is not None
    kv.close()
    assert _pragma(path, "page_size") == had


@pytest.mark.parametrize("path", (None, ":memory:"))
def test_a_page_size_without_a_file_is_ignored(path):
    kv = open_kv(path, page_size=16384)
    assert isinstance(kv, MemKV) and kv.page_bytes == 0
    kv.set(b"a", b"b")
    assert kv.get(b"a") == b"b"
    assert open_kv(path).page_bytes == 0


class _Told:
    """A connection that says what it was asked."""

    def __init__(self, conn, said):
        self._conn, self._said = conn, said

    def execute(self, sql, *args):
        self._said.append(sql)
        return self._conn.execute(sql, *args)

    def __getattr__(self, name):
        return getattr(self._conn, name)


OPENING = [
    "PRAGMA journal_mode=WAL",
    "PRAGMA synchronous=NORMAL",
    "CREATE TABLE IF NOT EXISTS kv (k BLOB PRIMARY KEY, v BLOB NOT NULL)",
]


@pytest.mark.parametrize("asked", (None, 16384))
def test_the_page_size_is_the_first_statement_or_no_statement(
        tmp_path, monkeypatch, asked):
    """Every store but the tx index passes nothing and runs the three
    statements it always ran, in their order; what follows only reads."""
    said = []
    connect = sqlite3.connect
    monkeypatch.setattr(
        kvmod.sqlite3, "connect",
        lambda *a, **kw: _Told(connect(*a, **kw), said))
    kv = open_kv(str(tmp_path / "s.db"), asked)
    kv.close()
    first = [] if asked is None else [f"PRAGMA page_size={asked}"]
    assert said == first + OPENING + ["PRAGMA page_size"]
