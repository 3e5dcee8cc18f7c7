"""Lightweight span/event tracer writing JSONL to a configurable sink.

The reference ships OpenTelemetry-style consensus tracing out of tree;
here a single-process JSONL tracer is enough to attribute wall time
across consensus steps, ApplyBlock stages, blocksync fetch→verify→apply
and crypto batch-verify dispatch (ISSUE 3 tentpole part 1). ISSUE 6
grows it into the data plane of the cross-node flight recorder: every
record carries a stable node identity, and p2p wire-message hooks give
the merger (utils/traceview.py) send→recv edges between sinks. ISSUE 24
makes the spans a tree on a clock the profiler shares.

Design constraints:

* Near-zero overhead when disabled. `enabled` is a plain module bool;
  hot paths guard with ``if trace.enabled:`` so the disabled cost is one
  global load. `span()` returns a shared no-op context manager so
  un-guarded ``with trace.span(...)`` sites stay cheap too.
* One JSON object per line. Every record carries ``ts`` (epoch seconds
  at emit, i.e. a span's END), ``pid`` (merge safety across e2e nodes),
  ``tid`` (threading.get_native_id() of the thread that wrote it),
  ``name`` and ``kind`` ("span" or "event"); spans add ``dur_ms``;
  callers attach free-form fields. Once `set_node()` ran, records also
  carry ``node`` — the cross-process join key the traceview merger
  aligns sinks on. The first record a thread writes into a sink is
  preceded by one ``trace.thread`` event (``tid``, ``thread`` = its
  name): a name costs one record a thread, not a field a record.
* A span made by `span()` is a node of a tree: ``id`` (unique in the
  process), ``parent`` (the span open on this thread when it began,
  null at a root), ``root`` (the id of its tree's root: the spans of one
  verify_commit, one ReplayEngine.run share it), ``t0_ns``/``t1_ns``
  from time.perf_counter_ns(), and ``self_ms``: its duration less what
  its direct children covered (each child adds its duration to its
  parent at exit). A ROOT span also carries ``cpu_ms``: what its thread
  spent on a CPU inside it (time.thread_time_ns() at both ends: native
  code it called included, another thread's turn at the interpreter and
  any sleep excluded). Only roots pay for that clock: it is the
  kernel's, a reading costs a quarter of a microsecond on Linux but
  5.6 us under a sandboxed kernel (gVisor, the benchmark's chip host),
  where it also steps by 10 ms, so that one span reads 0 or 10 and only
  a thread's sum over a window is a number (PERF.md, Findings PR 38).
  `tools/trace_analyze.py threads` sets that sum beside the thread's
  wall time and its time in the spans WAIT_SPANS names.
  A record written by emit() while a span is open on
  the thread carries that span as ``parent``/``root``. Work that is one
  unit but not nested in time shares a field instead (``window`` on the
  spans of a replay window, ``batch`` on the result() of a submit).
  A leg launched at one place and awaited at another, with other
  spans in between (a curve's share of a commit), is a span made by
  `open_span()`: the same ``id``/``parent``/``root``/``t0_ns``/``t1_ns``
  and ``dur_ms``, begun at the call and ended by its `close()`, but
  never on the thread's stack: no span nests under it, it carries no
  ``self_ms`` and leaves its parent's alone; it may end on another
  thread than it began on, so it carries no ``cpu_ms`` even at a root.
  configure() writes one ``trace.clock`` event pairing perf_counter_ns
  with time_ns, which puts every span on the wall clock.
* Under a profiler session the same spans lie in the profiler's trace:
  while tracing is enabled and jax is ALREADY imported, entering a span
  also enters jax.profiler.TraceAnnotation(name, span_id=id), on the
  profiler's own clock beside the device operations
  (utils/traceview.device_join reads both). This module never imports
  jax.
* Records are kept in memory and serialised by flush(): when
  FLUSH_INTERVAL_S has passed at a record that closes with no span open
  on its thread, when NESTED_FLUSH_INTERVALS of them have passed or
  MAX_BUFFERED records wait at one that closes inside a span, by
  `tail()`, `disable()` and at exit — never at every record (per-record
  flushing costs a syscall per consensus wire message once the p2p
  hooks are on), and as a rule not inside the span whose self time the
  work would be booked to. A flush takes the waiting records under the
  buffer's lock and serialises and writes them outside it, under a lock
  of the file's own: a record on another thread never waits for a
  json.dumps, and one that finds a flush under way leaves its own to
  the next. A SIGKILLed node loses at most the last
  interval's records (the last few under a long root span).
* Fork safety: ``pid`` is re-stamped and the sink reopened via an
  at-fork hook, so a process forked after configure() never stamps the
  parent's pid on its records (and never shares the parent's buffered
  file object or its unwritten records).
* Sink selection: `configure(path)` from node config
  (``[instrumentation] trace_sink``), or the ``COMETBFT_TPU_TRACE``
  environment variable at import time (picked up by subprocess nodes
  and bench.py without config plumbing).
"""

from __future__ import annotations

import atexit
import gc
import itertools
import json
import os
import sys
import threading
import time

enabled = False
_path: str | None = None
_fh = None
# the waiting records and the sink's identity (_fh, _path, _epoch).
# Re-entrant: a collection that starts while a record is queued runs
# _on_gc, which queues its own, on the same thread
_lock = threading.RLock()
# the file: one flush serialises and writes at a time, outside _lock.
# Taken BEFORE _lock, and never waited for by a record (_record tries
# it), so a thread that closes a span is never held for a json.dumps.
# Not re-entrant: a collection inside a flush must not start a second
_io_lock = threading.Lock()
_pid = os.getpid()
_node = ""
# configure() calls so far: a thread announces itself (trace.thread)
# once in every sink it writes to
_epoch = 0

# bounded write staleness: records wait in memory at most this long
# before a record that closes outside any span flushes them (see module
# docstring — per-record flush is too expensive once the p2p wire hooks
# multiply the record rate)
FLUSH_INTERVAL_S = 0.25
# ... and this many intervals, or this many records, before one that
# closes inside a span does (a replay holds one root span open for its
# whole run)
NESTED_FLUSH_INTERVALS = 8
MAX_BUFFERED = 8192
_last_flush = 0.0
_buf: list[dict] = []

_ids = itertools.count(1)
_annotation = None  # jax.profiler.TraceAnnotation once jax is imported

# a collection is recorded when it is a full one or pauses longer
GC_PAUSE_MIN_NS = 1_000_000


class _Thread(threading.local):
    """What the tracer keeps for each thread."""

    def __init__(self):
        self.stack: list = []  # its open spans, innermost last
        self.tid = threading.get_native_id()
        self.announced = 0  # the _epoch whose sink holds its trace.thread
        self.gc_span = None


_tls = _Thread()


def configure(path: str) -> None:
    """Open (append) the JSONL sink at `path` and enable tracing."""
    global enabled, _path, _fh, _pid, _last_flush, _epoch
    with _io_lock:
        _write()
        with _lock:
            if _fh is not None:
                _fh.close()
            d = os.path.dirname(path)
            if d:
                os.makedirs(d, exist_ok=True)
            _fh = open(path, "a", encoding="utf-8", buffering=1 << 16)
            _path = path
            _pid = os.getpid()
            _last_flush = 0.0
            _epoch += 1
            enabled = True
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    event("trace.clock", perf_ns=time.perf_counter_ns(),
          time_ns=time.time_ns())


def disable() -> None:
    global enabled, _path, _fh, _node
    with _io_lock:
        enabled = False
        _write()
        with _lock:
            if _fh is not None:
                _fh.close()
            _fh = None
            _path = None
            _node = ""
            del _buf[:]
    if _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


def path() -> str | None:
    return _path


def set_node(node_id: str) -> None:
    """Stamp a stable node identity (p2p node id) on every subsequent
    record. One identity per process: the first caller wins, so an
    in-process multi-node test doesn't flap the field mid-sink (its
    records are disambiguated by the per-message ``peer`` fields
    instead). Cleared by disable()."""
    global _node
    if not _node:
        _node = str(node_id)


def node_id() -> str:
    return _node


def _before_fork() -> None:
    # Write the records out in the parent so the child's inherited copy
    # of the buffers is empty — otherwise the child would re-write
    # records the parent also flushes later (duplicate lines in the
    # sink).
    try:
        flush()
    except Exception:  # noqa: BLE001 — fork must proceed regardless
        pass


def _after_fork_in_child() -> None:
    # A forked child must stamp its OWN pid and must not share the
    # parent's buffered file object (interleaved partial writes). The
    # locks are replaced too: another thread may have held one at fork
    # time, which would deadlock the child forever. The one thread that
    # lives on has a new native id and says so in the child's records.
    global _pid, _fh, _lock, _io_lock, _last_flush
    _lock = threading.RLock()
    _io_lock = threading.Lock()
    _pid = os.getpid()
    _tls.tid = threading.get_native_id()
    _tls.announced = 0
    _buf.clear()
    # first emit in the child flushes at once: multiprocessing children
    # exit via os._exit(), which skips atexit and buffered-file shutdown
    _last_flush = 0.0
    if _fh is not None:
        try:
            _fh.close()
        except OSError:
            pass
        try:
            _fh = open(_path, "a", encoding="utf-8", buffering=1 << 16) \
                if _path else None
        except OSError:
            _fh = None


if hasattr(os, "register_at_fork"):  # POSIX only; harmless otherwise
    os.register_at_fork(before=_before_fork,
                        after_in_child=_after_fork_in_child)


# one encoder for every record (json.dumps with these arguments builds
# one a call)
_encode = json.JSONEncoder(separators=(",", ":"), default=str).encode


def _serialise(batch: list[dict]) -> str:
    return "".join([_encode(rec) + "\n" for rec in batch])


def _write() -> None:
    """One flush; `_io_lock` is held. The waiting records leave the
    buffer under `_lock`; they are serialised and written outside it."""
    global _last_flush, _buf
    with _lock:
        fh = _fh
        _last_flush = time.monotonic()
        batch, _buf = _buf, []  # a record queued meanwhile waits its turn
    if fh is None:
        return
    if batch:
        fh.write(_serialise(batch))
    fh.flush()


def _envelope(name: str, kind: str) -> dict:
    """What every record carries, before anything of its own."""
    rec = {"ts": time.time(), "pid": _pid, "tid": _tls.tid, "name": name,
           "kind": kind}
    if _node:
        rec["node"] = _node
    return rec


def _record(rec: dict, nested: bool) -> None:
    """Queue one finished record, behind its thread's trace.thread if
    this sink has none yet; flush when the module docstring's staleness
    rule says so and no other flush is under way."""
    with _lock:
        if _fh is None:  # raced with disable()
            return
        if _tls.announced != _epoch:
            _tls.announced = _epoch
            _buf.append(dict(_envelope("trace.thread", "event"),
                             thread=threading.current_thread().name))
        _buf.append(rec)
        due = time.monotonic() - _last_flush
        if due < FLUSH_INTERVAL_S or (
                nested and len(_buf) < MAX_BUFFERED
                and due < NESTED_FLUSH_INTERVALS * FLUSH_INTERVAL_S):
            return
    if _io_lock.acquire(blocking=False):
        try:
            _write()
        finally:
            _io_lock.release()


def emit(name: str, kind: str = "event", **fields) -> None:
    """Queue one record. No-op (single bool check) when disabled. It
    hangs under the thread's innermost open span, unless the caller
    gives `parent` and `root` among its fields: a record written on
    one thread for a span of another (crypto.sched_wait)."""
    if not enabled:
        return
    rec = _envelope(name, kind)
    stack = _tls.stack
    if stack:
        rec["parent"] = stack[-1].id
        rec["root"] = stack[-1].root
    rec.update(fields)
    _record(rec, bool(stack))


def flush() -> None:
    """Force the waiting records to disk (readers that bypass tail());
    waits for a flush that another thread has under way."""
    with _io_lock:
        _write()


atexit.register(flush)  # records still waiting when the process ends


def event(name: str, **fields) -> None:
    emit(name, "event", **fields)


def _find_annotation():
    """jax.profiler.TraceAnnotation if jax is already imported (never
    imported from here: a node that has not touched jax stays off it)."""
    global _annotation
    jax = sys.modules.get("jax")
    cls = getattr(getattr(jax, "profiler", None), "TraceAnnotation", None)
    if cls is not None:
        _annotation = cls
    return cls


class _Span:
    __slots__ = ("name", "fields", "id", "parent", "root", "t0_ns",
                 "c0_ns", "_child_ns", "_ann")

    def __init__(self, name: str, fields: dict):
        self.name = name
        self.fields = fields
        self.id = next(_ids)
        self._child_ns = 0
        self._ann = None

    def add(self, **fields) -> None:
        self.fields.update(fields)

    def __enter__(self):
        self._open(True)
        return self

    def __exit__(self, *exc):
        self._close(0)
        return False

    def _open(self, annotate: bool) -> None:
        stack = _tls.stack
        if stack:
            self.parent = stack[-1].id
            self.root = stack[-1].root
        else:
            self.parent = None
            self.root = self.id
        stack.append(self)
        ann = annotate and (_annotation or _find_annotation())
        if ann:
            self._ann = ann(self.name, span_id=self.id)
            self._ann.__enter__()
        self.t0_ns = time.perf_counter_ns()
        # a root reads its thread's CPU clock, inside the wall clock's
        # two readings, so cpu_ms never passes dur_ms
        if self.parent is None:
            self.c0_ns = time.thread_time_ns()

    def _close(self, min_ns: int) -> None:
        """Pops the span and queues its record, unless it lasted less
        than `min_ns` (then it leaves no mark on its parent either)."""
        if self.parent is None:
            cpu_ns = time.thread_time_ns() - self.c0_ns
        t1_ns = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        stack = _tls.stack
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:  # closed out of order: drop it and the
            del stack[stack.index(self):]  # spans left open inside it
        dur_ns = t1_ns - self.t0_ns
        if dur_ns < min_ns or not enabled:
            return
        if stack:
            stack[-1]._child_ns += dur_ns
        rec = _span_record(self, t1_ns)
        rec["self_ms"] = round((dur_ns - self._child_ns) / 1e6, 3)
        if self.parent is None:
            rec["cpu_ms"] = round(cpu_ns / 1e6, 3)
        rec.update(self.fields)
        _record(rec, bool(stack))


def _span_record(sp, t1_ns: int) -> dict:
    """What every span record carries, before its own fields."""
    rec = _envelope(sp.name, "span")
    rec.update(id=sp.id, parent=sp.parent, root=sp.root, t0_ns=sp.t0_ns,
               t1_ns=t1_ns, dur_ms=round((t1_ns - sp.t0_ns) / 1e6, 3))
    return rec


class _NoopSpan:
    __slots__ = ()
    id = None

    def add(self, **fields) -> None:
        pass

    def close(self) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


def span(name: str, **fields):
    """Context manager timing a block; queues one span record on exit."""
    if not enabled:
        return _NOOP
    return _Span(name, fields)


class _OpenSpan:
    """A span in flight: begun by open_span(), ended by close(), off
    the thread's stack all the while (see the module docstring)."""

    __slots__ = ("name", "fields", "id", "parent", "root", "t0_ns")

    def __init__(self, name: str, fields: dict):
        self.name = name
        self.fields = fields
        self.id = next(_ids)
        stack = _tls.stack
        self.parent = stack[-1].id if stack else None
        self.root = stack[-1].root if stack else self.id
        self.t0_ns = time.perf_counter_ns()

    def add(self, **fields) -> None:
        self.fields.update(fields)

    def close(self) -> None:
        t1_ns = time.perf_counter_ns()
        if not enabled:
            return
        rec = _span_record(self, t1_ns)
        rec.update(self.fields)
        _record(rec, bool(_tls.stack))


def open_span(name: str, **fields):
    """Begins a span that stays open across other spans of its thread;
    its `close()` queues the record. The shared no-op when disabled."""
    if not enabled:
        return _NOOP
    return _OpenSpan(name, fields)


def _on_gc(phase: str, info: dict) -> None:
    """gc.callbacks hook (installed by configure()): one
    ``runtime.gc_pause`` span per full collection, or per pause over
    GC_PAUSE_MIN_NS, as a child of whatever span the collection
    interrupted — so that span's self time excludes the pause, and the
    record says WHICH span a long pause fell in. Only a full collection
    is worth an annotation in the profiler's trace."""
    if phase == "start":
        if enabled:
            full = info["generation"] == 2
            _tls.gc_span = sp = _Span(
                "runtime.gc_pause", {"generation": info["generation"]})
            sp._open(full)
        return
    sp = _tls.gc_span
    if sp is not None:
        _tls.gc_span = None
        sp.fields["collected"] = info.get("collected", 0)
        sp._close(0 if sp.fields["generation"] == 2 else GC_PAUSE_MIN_NS)


def tail(n: int = 100) -> list[dict]:
    """Last `n` parsed records from the sink (for the dump_trace RPC).

    The seek-back window starts at 256 KiB and grows geometrically until
    it holds `n` parseable lines or reaches the beginning of the file,
    so large `n` (or oversized records) can't silently come up short.
    A window that starts mid-file drops its first line — it may be a
    truncated record half — but at BOF the first line is kept."""
    p = _path
    if p is None or not os.path.exists(p):
        return []
    flush()
    with open(p, "rb") as f:
        window = 256 * 1024
        while True:
            # Re-measure every iteration: the sink can be truncated or
            # rotated under the reader (logrotate, a restarting node
            # reopening in "w" mode), and seeking against a stale size
            # would either raise or decode a window that no longer
            # exists as garbage half-lines.
            f.seek(0, os.SEEK_END)
            size = f.tell()
            start = max(0, size - window)
            f.seek(start)
            data = f.read(size - start)
            lines = data.decode("utf-8", "replace").splitlines()
            if start > 0 and lines:
                lines = lines[1:]
            out = []
            for line in lines:
                try:
                    out.append(json.loads(line))
                except ValueError:
                    continue
            if len(out) >= n or start == 0:
                return out[-n:]
            window *= 4


class TailReader:
    """Incremental follow-mode reader over a JSONL trace sink.

    `poll()` returns the records appended since the last call, holding
    any trailing partial line in a remainder buffer until its newline
    lands. Rotation/truncation-safe: when the file's current size drops
    below the saved offset the writer replaced or truncated the sink,
    so the reader resets to the beginning of the new file instead of
    seeking past EOF (the bug tail() had: a stale seek yields garbage).
    A missing file is not an error — the writer may not have started
    yet — poll() just returns nothing until it appears.
    """

    def __init__(self, path: str):
        self.path = path
        self._offset = 0
        self._rest = b""

    def poll(self, max_bytes: int = 4 << 20) -> list[dict]:
        try:
            with open(self.path, "rb") as f:
                f.seek(0, os.SEEK_END)
                size = f.tell()
                if size < self._offset:
                    # truncated or rotated under us: start over on the
                    # new contents and drop the stale partial line
                    self._offset = 0
                    self._rest = b""
                if size == self._offset:
                    return []
                f.seek(self._offset)
                chunk = f.read(min(size - self._offset, max_bytes))
        except OSError:
            return []
        self._offset += len(chunk)
        buf = self._rest + chunk
        lines = buf.split(b"\n")
        self._rest = lines.pop()  # b"" when chunk ended on a newline
        out = []
        for line in lines:
            if not line:
                continue
            try:
                out.append(json.loads(line.decode("utf-8", "replace")))
            except ValueError:
                continue
        return out


# ----------------------------------------------------------------------
# Span-name registry: every name passed to trace.span()/trace.event()/
# trace.emit() anywhere in the tree must be declared here, and every
# declared name must have a live call site — tools/trace_lint.py
# enforces both directions from the tier-1 suite. The flight-recorder
# analysis layer (utils/traceview.py, tools/trace_analyze.py) keys its
# reconstruction on these names, so renaming one is a cross-cutting
# change, not a local edit.
SPAN_REGISTRY = {
    "trace.clock": "written by configure(): perf_ns (time.perf_counter_ns, the clock of t0_ns/t1_ns) paired with time_ns (the wall clock of a profiler session's start)",
    "trace.thread": "written once a thread and sink, ahead of the first record the thread writes: tid (threading.get_native_id(), on every record) and thread (its name); tools/trace_analyze.py threads names its rows by it",
    "runtime.gc_pause": "one garbage collection that was full or paused over 1 ms, a child of the span it interrupted (generation/collected)",
    "node.boot": "node identity: moniker + full node id, once per process start",
    "consensus.step": "span closing the consensus step being left (height/round/dur_ms/next)",
    "consensus.finalize_commit": "block decided at height/round, with tx count",
    "consensus.propose_speculative": "one speculative proposal assembly overlapping the previous height's commit gap (height/txs/bytes)",
    "state.valset_update": "a block's validator updates applied to the set of two heights on, and the new set hashed (height/changes)",
    "state.apply_block": "ApplyBlock with validate/finalize/commit/save stage breakdown (validate_ms/finalize_ms/update_state_ms = the next state built: the validator updates and the proposer rotation/commit_ms = the app's Commit and the mempool's update/save_events_ms: five stages in order, which sum to dur_ms less the clock reads; data_hash_ms = inside validate_ms, the transactions' hashes and their Merkle root; state_save_ms = inside save_events_ms, the block's three state-store records encoded and written: the state with both validator sets, the results' hash, the encoded FinalizeBlockResponse, 0 without a state store; state_encode_ms = the part of state_save_ms spent building those bytes; state_write_ms = the part inside the key-value store's write_batch / set, the three commits; set_encodes = validator sets encoded afresh for those records, the rest of the five were looked up on a frozen set (ValidatorSet.encode; untraced nodes read state_valset_encode_total{memo}); tx_bytes = bytes of the block's transactions; rotation = column|integer: the arithmetic that rotated the proposer, ValidatorSet._rotate; publish_ms = inside save_events_ms, the event bus on this thread (EventBus.publish_block: the buffered subscribers' messages, if anyone subscribed, and the hand-over to the indexer) less index_wait_ms = what the indexer service held this thread back for, MAX_BLOCKS_HELD blocks published and not yet written; both 0 without a bus, the second 0 without an indexer; events = ABCI events in the block's FinalizeBlockResponse, its own and every result's, 0 from an application that emits none; response_bytes = that response as enc_finalize_resp encodes it for the state store, events included, 0 without a state store; untraced nodes read abci_events_total)",
    "index.block": "one block's events written by the indexer service, on its own thread, a root (height/txs/tx_bytes/keys = key-value pairs of the batch: a record and a height key a transaction, a key an indexed attribute, the block's record/bytes = their keys and values/attr_keys = of keys, the attribute keys: one a distinct (type.key, value) a transaction's events carried marked for indexing, 0 from an application that emits none/attr_bytes = of bytes, those keys with the hashes they point at and what the indexed attributes add to the records (field 6)/page_bytes = the size of the tx index file's pages, read once at open: TX_INDEX_PAGE_BYTES for a file made since PR 43, 4096 for one made before, 0 in memory/encode_ms = building the batch/write_ms = inside the two stores' write_batch and set: the tx index's one commit and the block index's/behind = blocks published and unwritten as this one's write ends, itself included: 1 when the service keeps up, MAX_BLOCKS_HELD when apply is being held back; untraced nodes read indexer_blocks_held, indexer_txs_indexed_total, indexer_blocks_indexed_total, indexer_attr_keys_total, indexer_events_dropped_total) (storage/indexer.py)",
    "types.verify_commit": "one verify_commit / verify_commit_light (height/n = signatures judged/light); self_ms is the entry layer from inside",
    "types.commit_items": "one commit turned into lanes: gates, address check, sign bytes (n/sign_bytes_ms = time inside the sign-bytes build/path = columnar: from the decode columns by validation.commit_lanes, no CommitSig built, or per_slot: the per-signature loop/reason, per_slot only = the gate that declined: no_columns, no_native, shape, key_type, address)",
    "types.verify_items_fill": "the lanes filled into their verifiers, up to the first submit: one add_batch plus the minority curves' rows on the columnar path, grouping by key type and the add() loop on the per-slot path (n/groups/singles)",
    "blocksync.block": "one fast-synced block: fetch→verify→apply breakdown",
    "blocksync.replay": "one ReplayEngine.run (from/to/depth/mode); self_ms is the engine loop",
    "blocksync.window_load": "blocks of one replay window read and decoded from the store (window = first height/blocks/end = full|set_change|tip|missing: what ended it/bytes = stored block bytes read/read_ms = inside the key-value gets/decode_ms = inside Block.decode)",
    "blocksync.window_queue": "every signature check of one replay window queued and submitted (window/blocks)",
    "blocksync.window_fill": "the commits of one window filled into the batch verifier (window/commits/lanes/columnar = commits on the columnar path)",
    "blocksync.window_resolve": "one window's verdict awaited and its +2/3 tallies checked (window/sigs)",
    "blocksync.set_change": "one boundary at which replay drained and queued anew, nothing in flight: from the end of the last apply before it to the return of the re-queued window's submit() (height/reason = set_change|speculation_failed)",
    "blocksync.window_apply": "the blocks of one verified window applied (window/blocks/txs/tx_bytes = bytes of those transactions); children state.apply_block",
    "crypto.batch_verify": "one batch-verify dispatch, the host time inside submit() (path/n/bucket); its children split it",
    "crypto.materialize": "lazy whole-commit columns expanded into per-item tuples for one dispatch (n = lanes expanded, 0 when add() already built them)",
    "crypto.pack": "R||S||k wire rows of one ladder or mesh dispatch built on the host (n/bucket/chunks = chunks the lanes went in/pool = run|busy|small|python: pooled, pool taken so packed inline, too few lanes, no native library)",
    "crypto.device_launch": "jax.device_put of one dispatch's wire arrays plus the jitted call's return (bytes/a_cache = hit: the batch's pubkey column was already decompressed on the device, miss: it ships and decompresses again; untraced nodes read crypto_a_cache_total{result})",
    "crypto.native_verify": "one batch judged by the host C++ engine, blame rescan included (n/ok)",
    "crypto.verdict_wait": "result() blocking on one batch's verdict (path/n/batch = id of its crypto.batch_verify/since_submit_ms/blame_rerun); path = sched: a caller's wait on a shared-scheduler handle (SchedPending.result), batch = id of the crypto.sched_coalesce its request rode in, wake_ms = from the instant the request's verdict was set (on the completion thread) to result() returning on the caller's, 0 where the verdict was in before the call",
    "crypto.commit_partition": "one curve's leg of one commit, launch to verdict, a child of types.verify_commit that its sibling legs overlap (curve/path/n/own_ms = the leg's own time on the thread that ran it: the host engine's call on its worker thread, or submit() plus the blocked result() of a device batch/waited_ms = what result() blocked the caller for)",
    "crypto.mesh_submit": "one sharded mega-batch across the verify mesh (n/b/n_devices/shard_lanes/a_cache = hit: the column's decompressed pair was on the shards, miss: the column ships, bytes of it, and the staging program runs before the verifier; untraced nodes read crypto_a_cache_total{result})",
    "crypto.stream_place": "one streamed commit placed on a mesh device (device/n/b)",
    "crypto.sched_collect": "the drainer between two dispatches, one a batch taken: from the entry of _collect to the return of _take, opened before and closed after the scheduler's lock (idle_ms = nothing queued/slot_ms = work queued and both slots of _MAX_UNANSWERED taken/linger_ms = the coalescing window and the pop that ends it: the three sum to dur_ms; the one the drainer is stopped in carries none of them) (crypto/sched.py)",
    "crypto.sched_coalesce": "one shared-scheduler dispatch on the drainer's thread: the merge and the launch (submit(); a cpu-backend or non-coalescable verifier verifies and answers inside it); it closes behind the launch, the verdict is the completion side's; its crypto.batch_verify and the requests' crypto.sched_wait are its children (n_requests/sigs/lanes_bucket/tenants/sources/per_tenant_sigs/absorb_ms = the merge loop, absent on the pass-through/inflight = earlier batches ON THE DEVICE and unanswered when the drainer took this one, 0 or 1: a host-engine batch ahead is not counted) (crypto/sched.py)",
    "crypto.sched_complete": "one launched batch on the completion side (the verify-sched-done thread; the caller's under drain_once): result(), the slices, the answers (batch = id of its crypto.sched_coalesce/n_requests/wait_ms = inside result()/since_launch_ms = submit() returned to last answer set)",
    "crypto.sched_wait": "one request through the shared scheduler, written where its verdict is set, as a child of the crypto.sched_coalesce it rode in whichever thread writes it: dur_ms = enqueue to verdict, queued_ms = enqueue to the moment _take_batch popped it (tenant/source/n/batch = id of that crypto.sched_coalesce/alone = true on the pass-through)",
    "mempool.admit_window": "one micro-batched admission window: n/dup/sig_fail/app_fail/admitted + stage ms",
    "tx.lifecycle": "one stage crossing of a sampled tx (tx/stage/mono; utils/txlife.py — hash-prefix sampled, correlated across nodes by tx)",
    "p2p.send": "consensus wire message handed to a peer (msg/height/round/peer)",
    "p2p.recv": "consensus wire message received from a peer (msg/height/round/peer)",
    "da.encode": "one committed payload erasure-coded + committed (height/bytes/shards/shard_bytes)",
    "da.pc_commit": "one payload committed on the 2D KZG track: per-column commitments + parity extension (height/rows/cols/bytes)",
    "replication.feed_send": "one committed height's frame fanned out on the replication feed (height/subs/bytes)",
    "replication.replica_apply": "one feed frame applied into replica serving state (height/da/dur_ms)",
    "consensus.conflicting_vote": "conflicting signed votes from one validator at one HRS (height/round/type/vote_a/vote_b hex) — the watchtower's equivocation feed",
    "watchtower.audit": "one audited feed frame: every check run against a height (node/height/checks/dur_ms)",
    "watchtower.verdict": "one watchtower finding (check/node/height/safety/detail) — safety verdicts fail an audited e2e run",
}

# The spans in which a thread does nothing but wait, for a verdict or
# for work: their self time is a wait the program chose. What is left of
# a thread's wall time inside its root spans, after those and after what
# it spent on a CPU (the roots' cpu_ms), is the interpreter's or the
# OS's: another thread's turn, a descheduled core, the C++ pool working
# for it. tools/trace_analyze.py threads splits a thread's time by it;
# tools/trace_lint.py holds it to registered names.
WAIT_SPANS = (
    "crypto.verdict_wait",
    "crypto.sched_collect",
)


# Kernel-scope registry: every phase of ops/ (a jax.named_scope) and
# every pallas_call name= there (a scope inside its phase) must be declared
# here and every declared name must be in use (tools/trace_lint.py, both
# directions). A scope is metadata of the
# compiled program: it reaches the profiler's trace as a component of
# each device operation's op_name, which is how utils/traceview
# device_join books device time to a phase that survives an edit to
# ops/ (HLO instruction numbers do not).
KERNEL_SCOPES = {
    "ladder.decompress": "per-lane program: ZIP-215 decoding of R (and of A in decompress_pubkeys)",
    "ladder.a_hi": "decompress_pubkeys: [2^128](-A), once a cached column (an A-cache miss), so the ladder runs 32 windows",
    "ladder.scalar_reduce": "per-lane program: signed-digit recoding of s and k (k arrives reduced mod L), S < L",
    "ladder.double_scalar": "per-lane program: [8]([s]B + [k](-A) - R), one fused kernel on the chip",
    "ladder.compare": "per-lane program: identity test, lane bitmap and its all-ok summary",
    "curve_decompress": "pallas kernel (ops/curve.py): fused sqrt candidate and checks",
    "curve_ladder_sub_mul8": "pallas kernel (ops/curve.py): the whole double-scalar ladder",
    "curve_mul_2_128": "pallas kernel (ops/curve.py): 128 doublings of a point, fused",
    "field_mul": "pallas kernel (ops/field.py): one 22-limb field multiply outside a fused kernel",
    "field_sq": "pallas kernel (ops/field.py): one field squaring outside a fused kernel",
}


_env = os.environ.get("COMETBFT_TPU_TRACE")
if _env:
    configure(_env)
del _env
