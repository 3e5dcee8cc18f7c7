"""Command-line interface (reference cmd/cometbft/commands/*).

Subcommands: init, start, testnet, show-node-id, show-validator,
gen-node-key, gen-validator, reset-all, version, inspect-lite.
Run via `python -m cometbft_tpu.cli <cmd> [--home DIR]`.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

VERSION = "0.3.0"  # round-3 line


def _cfg_paths(home: str):
    return {
        "config": os.path.join(home, "config"),
        "data": os.path.join(home, "data"),
        "config_file": os.path.join(home, "config", "config.toml"),
        "genesis": os.path.join(home, "config", "genesis.json"),
        "pv_key": os.path.join(home, "config", "priv_validator_key.json"),
        "pv_state": os.path.join(home, "data", "priv_validator_state.json"),
        "node_key": os.path.join(home, "config", "node_key.json"),
    }


def cmd_init(args) -> int:
    """reference commands/init.go: config + genesis + keys."""
    from .config import Config
    from .privval import FilePV
    from .types import Timestamp
    from .types.genesis import GenesisDoc, GenesisValidator

    p = _cfg_paths(args.home)
    os.makedirs(p["config"], exist_ok=True)
    os.makedirs(p["data"], exist_ok=True)
    cfg = Config()
    cfg.base.home = args.home
    cfg.base.chain_id = args.chain_id
    cfg.save(p["config_file"])
    pv = FilePV.generate(p["pv_key"], p["pv_state"])
    if not os.path.exists(p["genesis"]):
        gd = GenesisDoc(
            chain_id=args.chain_id,
            genesis_time=Timestamp.from_unix_ns(time.time_ns()),
            validators=[GenesisValidator(pv.pub_key().bytes(), 10, "validator")],
        )
        gd.save(p["genesis"])
    from .p2p import NodeKey

    NodeKey.load_or_generate(p["node_key"])
    print(f"initialized node home at {args.home}")
    return 0


def cmd_start(args) -> int:
    """reference commands/run_node.go."""
    from .abci.kvstore import KVStoreApp
    from .config import Config
    from .node import Node

    p = _cfg_paths(args.home)
    spec = os.environ.get("COMETBFT_TPU_LOG")
    if spec:
        from .utils.log import _LEVELS, set_level

        # validate the WHOLE spec before applying any of it: set_level
        # mutates per-segment, and a partial apply with an "ignoring"
        # message would silently leave earlier segments active
        parts = [s.strip() for s in spec.split(",") if s.strip()]
        bad = [
            s for s in parts
            if (s.partition(":")[2] or s) not in _LEVELS
        ]
        if bad:
            # a diagnostic knob typo must not keep the node down
            print(f"ignoring COMETBFT_TPU_LOG (bad level in {bad})",
                  file=sys.stderr)
        else:
            set_level(spec)
    cfg = Config.load(p["config_file"])
    cfg.base.home = args.home
    if getattr(args, "seed_mode", False):
        # flag overrides config (reference --p2p.seed_mode)
        cfg.p2p.seed_mode = True
        cfg.validate()
    # the built-in app answers as the reference's kvstore does: two `app`
    # events a transaction, which the node's indexer writes keys for
    app = (
        KVStoreApp(snapshot_interval=cfg.base.snapshot_interval, events=True)
        if cfg.base.abci == "local" else None
    )
    node = Node(cfg, app=app)
    node.start()
    print(f"node started: p2p {node.listen_addr}, rpc {getattr(node, 'rpc_addr', None)}")
    # SIGTERM (the e2e runner's and any supervisor's stop signal) takes
    # the same graceful path as ^C: stores close and the buffered trace
    # sink flushes instead of dying mid-write
    import signal as _signal

    def _term(_sig, _frm):
        raise KeyboardInterrupt

    _signal.signal(_signal.SIGTERM, _term)
    try:
        while True:
            time.sleep(1)
    except KeyboardInterrupt:
        node.stop()
    return 0


def cmd_testnet(args) -> int:
    """reference commands/testnet.go: N validator homes + shared genesis.

    With --seed-nodes K, K extra seed-mode homes (node{v}..node{v+K-1},
    NOT in the genesis validator set) follow the validator homes, and
    the validators get `p2p.seeds` pointing at them with NO persistent
    peers — the seed-only bootstrap topology the e2e runner exercises."""
    from .config import Config
    from .privval import FilePV
    from .types import Timestamp
    from .types.genesis import GenesisDoc, GenesisValidator

    n_seeds = getattr(args, "seed_nodes", 0)
    total = args.v + n_seeds
    key_type = getattr(args, "key_type", "ed25519")
    pv_key_type = (
        "tendermint/PubKeyBls12_381" if key_type == "bls"
        else "tendermint/PubKeyEd25519"
    )
    pvs = []
    homes = []
    for i in range(total):
        home = os.path.join(args.output, f"node{i}")
        p = _cfg_paths(home)
        os.makedirs(p["config"], exist_ok=True)
        os.makedirs(p["data"], exist_ok=True)
        pvs.append(FilePV.generate(p["pv_key"], p["pv_state"],
                                   key_type=pv_key_type))
        homes.append(home)
    gd = GenesisDoc(
        chain_id=args.chain_id,
        genesis_time=Timestamp.from_unix_ns(time.time_ns()),
        validators=[
            GenesisValidator(
                pv.pub_key().bytes(), 10, f"node{i}",
                pub_key_type=pv_key_type,
                # BLS genesis entries carry a proof of possession (rogue
                # -key defense — validated by GenesisDoc.validate_basic)
                pop=pv._priv.pop() if key_type == "bls" else b"",
            )
            for i, pv in enumerate(pvs[:args.v])
        ],
    )
    base_p2p = args.starting_port
    seed_addrs = [
        f"127.0.0.1:{base_p2p + 2 * (args.v + k)}" for k in range(n_seeds)
    ]
    for i, home in enumerate(homes):
        p = _cfg_paths(home)
        is_seed = i >= args.v
        cfg = Config()
        cfg.base.home = home
        cfg.base.chain_id = args.chain_id
        cfg.base.moniker = f"node{i}"
        cfg.p2p.laddr = f"tcp://127.0.0.1:{base_p2p + 2 * i}"
        cfg.rpc.laddr = f"tcp://127.0.0.1:{base_p2p + 2 * i + 1}"
        if is_seed:
            cfg.p2p.seed_mode = True
            # a seed may crawl its fellow seeds to widen its book
            cfg.p2p.seeds = ",".join(
                a for k, a in enumerate(seed_addrs) if k != i - args.v
            )
        elif n_seeds:
            # seed-only bootstrap: discovery must come through PEX
            cfg.p2p.seeds = ",".join(seed_addrs)
        else:
            cfg.p2p.persistent_peers = ",".join(
                f"127.0.0.1:{base_p2p + 2 * j}"
                for j in range(args.v) if j != i
            )
        cfg.save(p["config_file"])
        gd.save(p["genesis"])
    extra = f" + {n_seeds} seed homes" if n_seeds else ""
    print(f"generated {args.v} validator homes{extra} under {args.output}")
    return 0


def cmd_show_node_id(args) -> int:
    from .p2p import NodeKey

    p = _cfg_paths(args.home)
    print(NodeKey.load_or_generate(p["node_key"]).node_id())
    return 0


def cmd_show_validator(args) -> int:
    p = _cfg_paths(args.home)
    with open(p["pv_key"]) as f:
        d = json.load(f)
    print(json.dumps({"address": d["address"], "pub_key": d["pub_key"]}))
    return 0


def cmd_gen_node_key(args) -> int:
    from .p2p import NodeKey

    nk = NodeKey.generate()
    print(json.dumps({"id": nk.node_id()}))
    return 0


def cmd_gen_validator(args) -> int:
    from .privval import FilePV

    pv = FilePV.generate(None, None)
    print(json.dumps({
        "address": pv.pub_key().address().hex(),
        "pub_key": pv.pub_key().bytes().hex(),
    }))
    return 0


def cmd_reset_all(args) -> int:
    """reference commands/reset.go: wipe data, keep config + keys."""
    p = _cfg_paths(args.home)
    if os.path.isdir(p["data"]):
        for name in os.listdir(p["data"]):
            path = os.path.join(p["data"], name)
            if name == "priv_validator_state.json":
                continue
            if os.path.isdir(path):
                shutil.rmtree(path)
            else:
                os.unlink(path)
    os.makedirs(p["data"], exist_ok=True)
    with open(p["pv_state"], "w") as f:
        json.dump({"height": 0, "round": 0, "step": 0,
                   "signature": "", "sign_bytes": ""}, f)
    print("reset node data (privval last-sign state zeroed, keys kept)")
    return 0


def cmd_light(args) -> int:
    """reference cmd/cometbft/commands/light.go: run a light-client RPC
    proxy against a primary + witnesses, anchored at a trusted
    height/hash."""
    from .light import LightClient, LightStore
    from .light.provider_http import HTTPProvider
    from .light.proxy import LightProxy

    primary = HTTPProvider(args.chain_id, args.primary)
    witnesses = [
        HTTPProvider(args.chain_id, w)
        for w in (args.witnesses.split(",") if args.witnesses else [])
        if w
    ]
    lc = LightClient(
        args.chain_id, primary, witnesses=witnesses, store=LightStore(),
        trusting_period_s=args.trust_period,
        backend=args.backend,
    )
    lc.initialize(args.trusted_height, bytes.fromhex(args.trusted_hash))
    host, _, port = args.laddr.removeprefix("tcp://").rpartition(":")
    proxy = LightProxy(lc, host or "127.0.0.1", int(port or 0))
    proxy.start()
    print(f"light proxy serving verified RPC on {proxy.addr} "
          f"(primary {args.primary}, {len(witnesses)} witnesses)")
    try:
        while True:
            time.sleep(1)
    except KeyboardInterrupt:
        proxy.stop()
    return 0


def cmd_debug(args) -> int:
    """reference cmd/cometbft/commands/debug: capture a node's observable
    state over RPC into a tarball for post-mortem analysis."""
    import io
    import tarfile
    import urllib.request

    def rpc(method):
        body = json.dumps({"jsonrpc": "2.0", "id": 1, "method": method,
                           "params": {}}).encode()
        req = urllib.request.Request(
            args.rpc, data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.read()

    captured = {}
    for method in ("status", "net_info", "consensus_state",
                   "consensus_params", "num_unconfirmed_txs", "genesis"):
        try:
            captured[f"{method}.json"] = rpc(method)
        except Exception as e:  # noqa: BLE001 — capture what we can
            captured[f"{method}.error"] = str(e).encode()
    cfg_file = _cfg_paths(args.home)["config_file"]
    if os.path.exists(cfg_file):
        with open(cfg_file, "rb") as f:
            captured["config.toml"] = f.read()
    with tarfile.open(args.output, "w:gz") as tar:
        for name, data in captured.items():
            info = tarfile.TarInfo(name)
            info.size = len(data)
            info.mtime = int(time.time())
            tar.addfile(info, io.BytesIO(data))
    print(f"wrote {args.output} ({len(captured)} artifacts)")
    return 0


def cmd_compact_db(args) -> int:
    """reference commands/compact.go (experimental-compact-goleveldb):
    reclaim dead space in the node's sqlite stores."""
    import sqlite3

    p = _cfg_paths(args.home)
    n = 0
    for name in os.listdir(p["data"]):
        if not name.endswith(".db"):
            continue
        path = os.path.join(p["data"], name)
        before = os.path.getsize(path)
        con = sqlite3.connect(path)
        con.execute("VACUUM")
        con.close()
        after = os.path.getsize(path)
        print(f"{name}: {before} -> {after} bytes")
        n += 1
    if n == 0:
        print("no .db files under data/ (mem backend?)")
    return 0


def cmd_reindex_event(args) -> int:
    """reference commands/reindex_event.go: rebuild the tx and block
    indexes from the block store + stored ABCI responses (whose events
    give every attribute key back), a batch a block into the files a node
    with `[tx_index] indexer = "kv"` opens."""
    from .abci import wire as W
    from .config import Config
    from .storage import BlockStore, StateStore, open_kv
    from .storage.indexer import open_indexers

    p = _cfg_paths(args.home)
    cfg = Config.load(p["config_file"])
    mem = cfg.base.db_backend == "mem"
    if mem:
        print("mem backend holds no persisted blocks to reindex")
        return 1
    if cfg.tx_index.indexer != "kv":
        print(f'tx_index.indexer = "{cfg.tx_index.indexer}": this node '
              f"keeps no index to rebuild")
        return 1
    data = os.path.join(args.home, "data")
    bs = BlockStore(open_kv(os.path.join(data, "blockstore.db")))
    ss = StateStore(open_kv(os.path.join(data, "state.db")))
    txi, bli, dbs = open_indexers(data)
    start = args.start_height or bs.base() or 1
    end = args.end_height or bs.height()
    txs = blocks = 0
    for h in range(start, end + 1):
        blk = bs.load_block(h)
        raw = ss.load_abci_responses(h)
        if blk is None or raw is None:
            continue
        resp = W.dec_finalize_resp(raw)
        bli.index(h, resp.events)
        blocks += 1
        txs += txi.add_batch(h, blk.data.txs, resp.tx_results).txs
    for db in dbs:
        db.close()
    print(f"reindexed heights [{start}, {end}]: "
          f"{blocks} blocks, {txs} txs")
    return 0


def cmd_inspect_lite(args) -> int:
    """reference `cometbft inspect`: serve RPC over the stores of a
    stopped node, without consensus."""
    from .config import Config
    from .rpc.routes import Env
    from .rpc.server import RPCServer
    from .storage import BlockStore, StateStore, open_kv
    from .types.genesis import GenesisDoc

    p = _cfg_paths(args.home)
    cfg = Config.load(p["config_file"])
    mem = cfg.base.db_backend == "mem"
    bs = BlockStore(open_kv(None if mem else os.path.join(args.home, "data/blockstore.db")))
    ss = StateStore(open_kv(None if mem else os.path.join(args.home, "data/state.db")))
    env = Env(block_store=bs, state_store=ss,
              genesis_doc=GenesisDoc.load(p["genesis"]))
    host, port = cfg.rpc.laddr[len("tcp://"):].rsplit(":", 1)
    srv = RPCServer(env, host, int(port))
    srv.start()
    print(f"inspect rpc on {srv.addr} (ctrl-c to stop)")
    try:
        while True:
            time.sleep(1)
    except KeyboardInterrupt:
        srv.stop()
    return 0


def cmd_rollback(args) -> int:
    """reference `cometbft rollback`: overwrite state height n with n-1
    so block n re-applies (app state untouched)."""
    from .config import Config
    from .state.rollback import RollbackError, rollback
    from .storage import BlockStore, StateStore, open_kv

    p = _cfg_paths(args.home)
    cfg = Config.load(p["config_file"])
    mem = cfg.base.db_backend == "mem"
    bs = BlockStore(open_kv(None if mem else os.path.join(args.home, "data/blockstore.db")))
    ss = StateStore(open_kv(None if mem else os.path.join(args.home, "data/state.db")))
    try:
        height, app_hash = rollback(bs, ss, remove_block=args.hard)
    except RollbackError as e:
        print(f"rollback failed: {e}")
        return 1
    print(f"rolled back state to height {height} (app hash {app_hash.hex()})")
    return 0


def cmd_bootstrap_state(args) -> int:
    """reference `cometbft bootstrap-state` (node/node.go:150-259): seed
    a fresh node's state store from light-client-verified state so it
    block-syncs from there instead of replaying from genesis."""
    from .config import Config
    from .node.node import bootstrap_state

    p = _cfg_paths(args.home)
    cfg = Config.load(p["config_file"])
    cfg.base.home = args.home
    try:
        h = bootstrap_state(
            cfg,
            height=args.height,
            rpc_servers=args.servers,
            trust_height=args.trust_height,
            trust_hash=args.trust_hash,
        )
    except Exception as e:  # noqa: BLE001 — operator tool
        print(f"bootstrap-state failed: {e}")
        return 1
    print(f"bootstrapped state at height {h}")
    return 0


def cmd_replica(args) -> int:
    """Stateless serving replica (replication/replica.py, ROADMAP #3):
    bootstrap from a core node's replication snapshot, tail its feed,
    and serve the light/DA surfaces byte-identically with zero
    consensus state. Prints one JSON line with the bound addresses so
    drivers (the load tools' --endpoints) can discover the
    ephemeral ports."""
    from .replication import Replica

    cfg = None
    cfg_file = _cfg_paths(args.home)["config_file"]
    if os.path.exists(cfg_file):
        from .config import Config

        cfg = Config.load(cfg_file)
    rep_cfg = cfg.replication if cfg is not None else None
    core_url = args.core_url or (rep_cfg.core_url if rep_cfg else "")
    if not core_url:
        print("replica: --core-url (or [replication] core_url) required",
              file=sys.stderr)
        return 1
    host, _, port = args.laddr.removeprefix("tcp://").rpartition(":")
    mhost, _, mport = args.metrics_laddr.rpartition(":")
    rep = Replica(
        core_url,
        name=(args.name
              or (rep_cfg.tenant if rep_cfg else "")
              or f"replica-{os.getpid()}"),
        backend=args.backend,
        rpc_host=host or "127.0.0.1",
        rpc_port=int(port or 0),
        metrics_host=mhost or "127.0.0.1",
        metrics_port=int(mport or 0),
        retain_frames=(rep_cfg.retain_frames if rep_cfg else 1024),
        max_lag_heights=(args.max_lag_heights
                         if args.max_lag_heights is not None
                         else (rep_cfg.max_lag_heights if rep_cfg else 16)),
        forward_admission=(not args.no_forward) and (
            rep_cfg.forward_admission if rep_cfg else True),
    )
    try:
        rep.start()
    except Exception as e:  # noqa: BLE001 — operator-facing boot error
        print(f"replica failed to start: {e}", file=sys.stderr)
        return 1
    print(json.dumps({
        "name": rep.name,
        "rpc": list(rep.rpc_addr),
        "metrics": list(rep.metrics_addr) if rep.metrics_addr else None,
        "core": core_url,
    }), flush=True)
    import signal as _signal

    def _term(_sig, _frm):
        raise KeyboardInterrupt

    _signal.signal(_signal.SIGTERM, _term)
    try:
        while True:
            time.sleep(1)
    except KeyboardInterrupt:
        rep.stop()
    return 0


def _parse_named(spec: str, prefix: str) -> dict[str, str]:
    """Parse "name=value,name=value" (bare values get prefix0..N)."""
    out: dict[str, str] = {}
    for i, part in enumerate(p for p in spec.split(",") if p.strip()):
        part = part.strip()
        if "=" in part:
            name, _, value = part.partition("=")
            out[name.strip()] = value.strip()
        else:
            out[f"{prefix}{i}"] = part
    return out


def cmd_watchtower(args) -> int:
    """Streaming safety auditor (watchtower/, ROADMAP #5): tail N core
    nodes' replication feeds + optional trace sinks, continuously check
    forks / equivocation / certificates / data availability / live
    stalls, and emit structured verdicts. Shaped like a replica
    process-wise — prints one JSON discovery line, serves /metrics +
    /healthz, exits on SIGTERM — but holds no serving state at all."""
    from .utils.metrics import MetricsServer
    from .watchtower import Watchtower

    wt_cfg = None
    cfg_file = _cfg_paths(args.home)["config_file"]
    if os.path.exists(cfg_file):
        from .config import Config

        wt_cfg = Config.load(cfg_file).watchtower
    nodes_spec = args.nodes or (wt_cfg.node_urls if wt_cfg else "")
    if not nodes_spec:
        print("watchtower: --nodes (or [watchtower] node_urls) required",
              file=sys.stderr)
        return 1
    nodes = _parse_named(nodes_spec, "node")
    sinks = _parse_named(
        args.trace_sinks or (wt_cfg.trace_sinks if wt_cfg else ""), "node")
    wt = Watchtower(
        nodes,
        trace_sinks=sinks,
        full_commit_window=(wt_cfg.full_commit_window if wt_cfg else 16),
        da_interval_s=(wt_cfg.da_interval_s if wt_cfg else 2.0),
        da_samples=(wt_cfg.da_samples if wt_cfg else 4),
        da_alarm_after=(wt_cfg.da_alarm_after if wt_cfg else 2),
        stall_interval_s=(wt_cfg.stall_interval_s if wt_cfg else 1.0),
        verdict_path=(args.verdict_path
                      or (wt_cfg.verdict_path if wt_cfg else "")),
    )
    wt.start()
    mhost, _, mport = args.metrics_laddr.rpartition(":")
    srv = MetricsServer(
        host=mhost or "127.0.0.1", port=int(mport or 0),
        height_fn=lambda: max(
            (n["audited"] for n in wt.status()["nodes"].values()),
            default=0),
        ready_fn=wt.ready,
    )
    srv.start()
    print(json.dumps({
        "watchtower": True,
        "nodes": nodes,
        "metrics": list(srv.addr),
        "verdict_path": wt.verdict_path or None,
    }), flush=True)
    import signal as _signal

    def _term(_sig, _frm):
        raise KeyboardInterrupt

    _signal.signal(_signal.SIGTERM, _term)
    try:
        while True:
            time.sleep(1)
    except KeyboardInterrupt:
        srv.stop()
        wt.stop()
    return 0


def cmd_version(args) -> int:
    print(VERSION)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="cometbft_tpu")
    ap.add_argument("--home", default=os.path.expanduser("~/.cometbft_tpu"))
    sub = ap.add_subparsers(dest="cmd", required=True)

    sp = sub.add_parser("init");  sp.add_argument("--chain-id", default="local-chain"); sp.set_defaults(fn=cmd_init)
    sp = sub.add_parser("start")
    sp.add_argument("--seed-mode", action="store_true",
                    help="run as a seed-crawler (overrides p2p.seed_mode)")
    sp.set_defaults(fn=cmd_start)
    sp = sub.add_parser("testnet")
    sp.add_argument("--v", type=int, default=4)
    sp.add_argument("--seed-nodes", type=int, default=0,
                    help="extra non-validator seed-mode homes; validators "
                         "then bootstrap via p2p.seeds instead of "
                         "persistent_peers")
    sp.add_argument("--output", default="./testnet")
    sp.add_argument("--chain-id", default="testnet-chain")
    sp.add_argument("--starting-port", type=int, default=26656)
    sp.add_argument("--key-type", default="ed25519",
                    choices=("ed25519", "bls"),
                    help="validator consensus key curve; bls enables "
                         "certificate-native commits (genesis carries "
                         "possession proofs)")
    sp.set_defaults(fn=cmd_testnet)
    sub.add_parser("show-node-id").set_defaults(fn=cmd_show_node_id)
    sub.add_parser("show-validator").set_defaults(fn=cmd_show_validator)
    sub.add_parser("gen-node-key").set_defaults(fn=cmd_gen_node_key)
    sub.add_parser("gen-validator").set_defaults(fn=cmd_gen_validator)
    sub.add_parser("reset-all").set_defaults(fn=cmd_reset_all)
    sub.add_parser("inspect-lite").set_defaults(fn=cmd_inspect_lite)
    sub.add_parser("inspect").set_defaults(fn=cmd_inspect_lite)
    sp = sub.add_parser("light")
    sp.add_argument("chain_id")
    sp.add_argument("--primary", required=True)
    sp.add_argument("--witnesses", default="")
    sp.add_argument("--trusted-height", type=int, required=True)
    sp.add_argument("--trusted-hash", required=True)
    sp.add_argument("--trust-period", type=int, default=7 * 24 * 3600)
    sp.add_argument("--laddr", default="tcp://127.0.0.1:8888")
    sp.add_argument("--backend", default="cpu")
    sp.set_defaults(fn=cmd_light)
    sp = sub.add_parser("debug")
    sp.add_argument("--rpc", default="http://127.0.0.1:26657")
    sp.add_argument("--output", default="cometbft-debug.tar.gz")
    sp.set_defaults(fn=cmd_debug)
    sub.add_parser("compact-db").set_defaults(fn=cmd_compact_db)
    sp = sub.add_parser("reindex-event")
    sp.add_argument("--start-height", type=int, default=0)
    sp.add_argument("--end-height", type=int, default=0)
    sp.set_defaults(fn=cmd_reindex_event)
    sp = sub.add_parser("rollback")
    sp.add_argument("--hard", action="store_true",
                    help="also remove the pending block from the block store")
    sp.set_defaults(fn=cmd_rollback)
    sp = sub.add_parser("bootstrap-state")
    sp.add_argument("--height", type=int, default=0,
                    help="state height to bootstrap (0 = latest - 2)")
    sp.add_argument("--servers", default="",
                    help="comma-separated RPC endpoints "
                         "(default: statesync.rpc_servers)")
    sp.add_argument("--trust-height", type=int, default=0)
    sp.add_argument("--trust-hash", default="")
    sp.set_defaults(fn=cmd_bootstrap_state)
    sp = sub.add_parser("replica")
    sp.add_argument("--core-url", default="",
                    help="http://host:port of the core node's RPC "
                         "(default: [replication] core_url)")
    sp.add_argument("--laddr", default="tcp://127.0.0.1:0",
                    help="replica RPC listen address (port 0 = ephemeral)")
    sp.add_argument("--metrics-laddr", default="127.0.0.1:0",
                    help="metrics/healthz listen address")
    sp.add_argument("--name", default="",
                    help="replica tenant name on the shared scheduler")
    sp.add_argument("--backend", default="cpu", choices=("cpu", "tpu"))
    sp.add_argument("--max-lag-heights", type=int, default=None,
                    help="healthz turns 503 past this feed lag")
    sp.add_argument("--no-forward", action="store_true",
                    help="disable broadcast_tx_* admission forwarding")
    sp.set_defaults(fn=cmd_replica)
    sp = sub.add_parser("watchtower")
    sp.add_argument("--nodes", default="",
                    help="comma-separated name=http://host:port feeds to "
                         "audit (default: [watchtower] node_urls)")
    sp.add_argument("--trace-sinks", default="",
                    help="comma-separated name=/path/to/trace.jsonl for "
                         "the live stall classifier")
    sp.add_argument("--metrics-laddr", default="127.0.0.1:0",
                    help="metrics/healthz listen address")
    sp.add_argument("--verdict-path", default="",
                    help="append verdicts as JSONL here as well")
    sp.set_defaults(fn=cmd_watchtower)
    sub.add_parser("version").set_defaults(fn=cmd_version)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
