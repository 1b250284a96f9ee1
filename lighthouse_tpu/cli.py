"""Top-level CLI (reference lighthouse/src/main.rs:348-617 clap tree:
`lighthouse {bn,vc,am,db}` + the lcli dev tools): argparse subcommands
wiring the same component stacks the tests drive in-process.

Entry: python -m lighthouse_tpu.cli <subcommand> ...
"""
# lint: allow-file[wallclock] -- process entry point: wall clock enters
# here (genesis defaults, startup deadlines, tool timing) and is handed
# to the rest of the system as SystemSlotClock / genesis_time

from __future__ import annotations

import argparse
import json
import sys
import time


def _spec_preset(args):
    from .types import ChainSpec, MAINNET, MINIMAL
    from .types.presets import GNOSIS

    preset = {"minimal": MINIMAL, "mainnet": MAINNET, "gnosis": GNOSIS}[
        args.preset
    ]
    if args.network == "gnosis" and args.preset != "gnosis":
        preset = GNOSIS  # the network pins its own compile-time preset
    if args.network == "interop":
        spec = ChainSpec.interop(
            altair_fork_epoch=args.altair_fork_epoch
        )
    else:
        spec = ChainSpec.network(args.network)
    return preset, spec


def _lock_datadir(datadir: str) -> int:
    """Exclusive advisory lock on <datadir>/LOCK (the seat of LevelDB's
    LOCK file): one process per datadir. Without it, `db fsck`'s
    open-time journal recovery racing a live node's in-flight batch
    could replay the intent record and delete the journal row out from
    under the node — whose crash then reopens "clean" with a torn batch,
    exactly the state the WAL exists to rule out. Returns the held fd;
    the caller keeps it referenced so the lock lives as long as the
    process."""
    import fcntl
    import os

    os.makedirs(datadir, exist_ok=True)
    fd = os.open(os.path.join(datadir, "LOCK"), os.O_CREAT | os.O_RDWR)
    try:
        fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
    except OSError:
        os.close(fd)
        raise SystemExit(
            f"datadir {datadir!r} is locked by another process (a running "
            "node?); stop it before running this command"
        )
    return fd


def _add_network_args(p):
    p.add_argument("--network", default="interop",
                   choices=["interop", "minimal", "mainnet", "sepolia",
                            "prater", "goerli", "gnosis"])
    p.add_argument("--preset", default="minimal",
                   choices=["minimal", "mainnet", "gnosis"])
    p.add_argument("--altair-fork-epoch", type=int, default=None)
    p.add_argument("--log-level", default="info",
                   choices=["trace", "debug", "info", "warn", "error"])
    p.add_argument("--log-json", action="store_true")


# --- beacon node ------------------------------------------------------------


def build_eth1_service(args):
    """Eth1Service over the JSON-RPC provider when --eth1-endpoint is
    given (reference eth1/src/service.rs polling service)."""
    if not getattr(args, "eth1_endpoint", None):
        return None
    from .eth1 import Eth1Service
    from .eth1.jsonrpc import JsonRpcEth1Provider

    import sys

    provider = JsonRpcEth1Provider(args.eth1_endpoint)
    svc = Eth1Service(provider)
    try:
        svc.update()
    except Exception as e:  # noqa: BLE001 -- endpoint flap must not kill startup
        print(
            f"warning: eth1 endpoint {args.eth1_endpoint} unreachable: {e}",
            file=sys.stderr,
        )
    return svc


def resolve_genesis(args, store, preset, spec, eth1_service=None):
    """ClientGenesis resolution (reference client/src/config.rs:15-40 +
    builder.rs:206-340): interop keys, FromStore restart resume, or a
    weak-subjectivity checkpoint (finalized state+block SSZ)."""
    from .chain.beacon_chain import BeaconChain
    from .types import interop_genesis_state
    from .utils.slot_clock import SystemSlotClock

    mode = getattr(args, "genesis", "interop")
    if mode == "resume":
        chain = BeaconChain.from_store(store, preset, spec)
        chain.slot_clock = SystemSlotClock(
            chain.head_state.genesis_time, spec.seconds_per_slot
        )
        return chain
    if mode == "checkpoint":
        from .types import decode_state_any_fork, decode_block_any_fork

        if not getattr(args, "checkpoint_state", None) or not getattr(
            args, "checkpoint_block", None
        ):
            raise SystemExit(
                "--genesis checkpoint requires --checkpoint-state and "
                "--checkpoint-block"
            )
        with open(args.checkpoint_state, "rb") as f:
            state = decode_state_any_fork(f.read(), preset)
        with open(args.checkpoint_block, "rb") as f:
            block = decode_block_any_fork(f.read(), preset)
        chain = BeaconChain.from_anchor(store, state, block, preset, spec)
        chain.slot_clock = SystemSlotClock(
            state.genesis_time, spec.seconds_per_slot
        )
        return chain
    if mode == "checkpoint-url":
        # ClientGenesis::CheckpointSyncUrl (builder.rs:206-340): fetch the
        # finalized state+block pair from a trusted node's HTTP API
        from .http_api import BeaconNodeHttpClient

        url = getattr(args, "checkpoint_sync_url", None)
        if not url:
            raise SystemExit(
                "--genesis checkpoint-url requires --checkpoint-sync-url"
            )
        client = BeaconNodeHttpClient(url, preset)
        state, block = client.fetch_checkpoint_anchor()
        chain = BeaconChain.from_anchor(store, state, block, preset, spec)
        chain.slot_clock = SystemSlotClock(
            state.genesis_time, spec.seconds_per_slot
        )
        return chain
    if mode == "deposit-contract":
        # ClientGenesis::DepositContract: poll the deposit contract until
        # a valid genesis forms (reference beacon_node/genesis service)
        from .state_transition.genesis import try_genesis_from_eth1

        if eth1_service is None:
            raise SystemExit(
                "--genesis deposit-contract requires --eth1-endpoint"
            )
        import sys

        timeout_s = getattr(args, "genesis_timeout", None)
        deadline = time.time() + (
            600.0 if timeout_s is None else float(timeout_s)
        )
        update_failures = 0
        # lint: allow[retry-no-backoff] -- deadline-bounded genesis poll
        # (the SystemExit below caps it); the fixed 2s cadence IS the
        # genesis-detection interval, not a transport retry
        while True:
            state = try_genesis_from_eth1(eth1_service, preset, spec)
            if state is not None:
                break
            if time.time() > deadline:
                hint = (
                    f" ({update_failures} eth1 update failures -- is the "
                    f"endpoint reachable?)"
                    if update_failures
                    else ""
                )
                raise SystemExit(
                    f"no valid genesis formed before timeout{hint}"
                )
            time.sleep(2.0)
            try:
                eth1_service.update()
            except Exception as e:  # noqa: BLE001 -- keep waiting through flaps
                update_failures += 1
                if update_failures in (1, 10) or update_failures % 100 == 0:
                    print(
                        f"warning: eth1 update failed ({update_failures}x): {e}",
                        file=sys.stderr,
                    )
                continue
        clock = SystemSlotClock(state.genesis_time, spec.seconds_per_slot)
        return BeaconChain(store, state, preset, spec, slot_clock=clock)
    genesis = interop_genesis_state(
        args.interop_validators, preset, spec,
        genesis_time=args.genesis_time or int(time.time()),
    )
    clock = SystemSlotClock(genesis.genesis_time, spec.seconds_per_slot)
    return BeaconChain(store, genesis, preset, spec, slot_clock=clock)


def build_beacon_node(args):
    """ClientBuilder equivalent (reference client/src/builder.rs:56):
    store -> genesis -> chain -> pools -> API server."""
    from .http_api import BeaconApi, BeaconApiServer
    from .store.hot_cold import HotColdDB
    from .store.kv import MemoryStore
    from .validator_client.beacon_node import InProcessBeaconNode

    preset, spec = _spec_preset(args)
    # persistent XLA compile cache: the verifier's stage compiles are
    # paid once per checkout, not once per process (utils/compile_cache
    # decides the location; disk-warm shapes surface as
    # tpu_compile_cache_hits_total on restart)
    from .utils.compile_cache import arm as _arm_compile_cache

    _arm_compile_cache()
    if args.datadir:
        import os

        # held (via the args reference) for the life of the node
        args._datadir_lock = _lock_datadir(args.datadir)
        native_path = os.path.join(args.datadir, "chain.db")
        if os.path.isdir(args.datadir) and not os.path.exists(
            native_path
        ) and any(
            os.path.isdir(os.path.join(args.datadir, d))
            for d in ("chn", "blk", "ste")
        ):
            # legacy FileStore datadir: keep reading it rather than
            # silently abandoning its chain under a fresh chain.db
            from .store.kv import FileStore

            kv = FileStore(args.datadir)
        else:
            # embedded C++ log-structured store (the LevelDB seat)
            from .store.native_kv import NativeStore

            os.makedirs(args.datadir, exist_ok=True)
            kv = NativeStore(native_path)
    else:
        kv = MemoryStore()
    store = HotColdDB(kv, preset, spec)
    eth1_service = build_eth1_service(args)
    chain = resolve_genesis(args, store, preset, spec, eth1_service)
    from .utils.logging import Logger

    log = Logger(
        level=getattr(args, "log_level", "info"),
        json_lines=getattr(args, "log_json", False),
    ).child(service="bn")
    node = InProcessBeaconNode(chain, eth1_service=eth1_service, log=log)
    # optional wire networking (lighthouse_network seat): a TCP listener
    # plus bootnode discovery turns this process into a networked peer
    if getattr(args, "listen_port", None) is not None or getattr(
        args, "bootnode", None
    ):
        from .network import NetworkNode, WireBus

        bus = WireBus(preset)
        peer_id = getattr(args, "peer_id", None) or f"bn-{id(chain) & 0xFFFF}"
        # ONE operation pool: gossip ingestion and API/VC block production
        # must see the same operations (and one persisted blob on shutdown)
        node.network = NetworkNode(peer_id, chain, bus, op_pool=node.op_pool)
        bus.listen(peer_id, getattr(args, "listen_port", 0) or 0)
        if getattr(args, "bootnode", None):
            host, _, port = args.bootnode.partition(":")
            bus.bootstrap((host, int(port)))
            node.network.range_sync()
        node.wire_bus = bus
    api = BeaconApi(node, network=getattr(node, "network", None))
    from .serving import ServingConfig

    serving_config = ServingConfig(
        cache_enabled=not getattr(args, "serving_no_cache", False),
        cache_max_entries=getattr(args, "serving_cache_entries", 512),
        sse_max_subscribers=getattr(args, "serving_max_subscribers", 64),
        queue_wait_p95_threshold_s=getattr(
            args, "serving_queue_wait_p95", 0.5
        ),
        slot_delay_p95_threshold_s=getattr(
            args, "serving_slot_delay_p95", 4.0
        ),
        retry_after_s=getattr(args, "serving_retry_after", 1),
    )
    network = getattr(node, "network", None)
    if getattr(args, "speculate", False):
        # duty-driven precompute + idle-time speculation (speculate/):
        # committee aggregate pubkeys are built at every epoch boundary
        # and the processor's idle seam pre-verifies expected next-slot
        # aggregates (when a signature source is wired; precompute alone
        # already removes per-set pubkey aggregation from the hot path)
        from .speculate import attach_speculation

        attach_speculation(
            chain,
            processor=getattr(network, "processor", None),
            queue_wait_p95_max=getattr(
                args, "speculate_queue_wait_p95", 0.05
            ),
        )
    server = BeaconApiServer(
        api,
        port=args.http_port,
        serving_config=serving_config,
        processor=getattr(network, "processor", None),
    )
    return node, server


def cmd_bn(args):
    from .utils.executor import TaskExecutor
    from .utils.logging import Logger

    log = Logger(level=args.log_level, json_lines=args.log_json).child(
        service="bn"
    )
    node, server = build_beacon_node(args)
    if getattr(args, "warm_compile", False):
        # warm BEFORE serving: every bucketed verifier shape compiles (or
        # loads from the armed compile cache) now, so the first slot's
        # batches hit only warm executables
        from .crypto.bls.backends.jax_tpu import warm_compile

        for row in warm_compile():
            log.info("warm bucket", bucket="x".join(
                str(v) for v in row["bucket"]
            ), seconds=round(row["seconds"], 3), compiled=row["compiled"])
    server.start()
    log.info("beacon node started", http_port=server.port,
             validators=len(node.chain.head_state.validators))
    if args.dry_run:
        server.stop()
        return 0

    # service threads on the executor (environment + task_executor seat):
    # the notifier and gossip drain run as tracked tasks; ctrl-c or a task
    # failure broadcasts shutdown and everything joins
    executor = TaskExecutor("bn")

    if hasattr(node, "network"):
        # event-driven gossip processing (beacon_processor.rs worker pool);
        # >1 worker lets a slow block import overlap attestation batches
        node.network.processor.start(
            num_workers=getattr(args, "processor_workers", 1)
        )

    def notifier():  # client/src/notifier.rs
        head = node.chain.head_state
        log.info("status", slot=node.chain.current_slot, head=head.slot,
                 finalized=node.chain.finalized_checkpoint[0])

    def tick():
        node.chain.on_tick()
        if node.eth1_service is not None:
            # deposit-log polling (eth1/src/service.rs update loop)
            try:
                node.eth1_service.update()
            except Exception as e:  # noqa: BLE001 -- eth1 node flaps
                log.warn("eth1 update failed", error=str(e))
        if hasattr(node, "network") and not node.network.processor.is_running:
            # no worker pool running (dry-run / embedded use): drain gossip
            # work inline (the BeaconProcessor worker seat)
            node.network.processor.run_until_idle()
        elif getattr(node.chain, "speculation", None) is not None and hasattr(
            node, "network"
        ):
            # worker pool mode: run_until_idle never fires here, so the
            # tick loop offers the speculation idle slot itself (the
            # processor still refuses unless genuinely idle)
            node.network.processor.run_idle_task()

    executor.spawn_loop(tick, "per-slot", node.spec.seconds_per_slot)
    executor.spawn_loop(notifier, "notifier", node.spec.seconds_per_slot)
    monitoring = None
    if getattr(args, "monitoring_endpoint", None):
        from .utils.monitoring import MonitoringService, beacon_node_source

        monitoring = MonitoringService(
            args.monitoring_endpoint,
            data_sources={
                "beacon_node": lambda: beacon_node_source(
                    node.chain, serving=server.serving
                )
            },
        ).start()
        log.info("monitoring pushes enabled", endpoint=args.monitoring_endpoint)
    rc = 0
    try:
        executor.wait_shutdown()
        reason = executor.shutdown_reason()
        if reason is not None and reason.failure:
            log.crit("shutting down on failure", reason=reason.message)
            rc = 1  # supervisors must see the failure
    except KeyboardInterrupt:
        executor.shutdown("ctrl-c")
        log.info("shutting down")
    if monitoring is not None:
        monitoring.stop()
    server.stop()
    executor.join_all()
    if hasattr(node, "network") and node.network.processor.is_running:
        node.network.processor.stop()
    # pooled operations survive the restart (persistence.rs shutdown hook)
    try:
        node.op_pool.persist(node.chain.store)
        log.info("operation pool persisted",
                 attestations=node.op_pool.num_attestations())
    except Exception as e:  # noqa: BLE001 -- persistence is best-effort
        log.warn("op-pool persist failed", error=str(e))
    return rc


# --- validator client -------------------------------------------------------


def cmd_vc(args):
    from .http_api import BeaconNodeHttpClient
    from .types import interop_secret_key
    from .validator_client import (
        BeaconNodeFallback, LocalKeystore, ValidatorClient, ValidatorStore,
    )
    from .crypto.keystore import Keystore

    preset, spec = _spec_preset(args)
    nodes = BeaconNodeFallback([
        BeaconNodeHttpClient(url, preset) for url in args.beacon_nodes
    ])
    store = ValidatorStore(preset, spec)
    count = 0
    if args.interop_validators:
        lo, _, hi = args.interop_validators.partition("..")
        for i in range(int(lo), int(hi)):
            store.add_validator(LocalKeystore(interop_secret_key(i)))
            count += 1
    for path in args.keystores or []:
        with open(path) as f:
            ks = Keystore.from_json(f.read())
        store.add_validator(LocalKeystore(ks.decrypt(args.password or "")))
        count += 1
    vc = ValidatorClient(
        store,
        nodes,
        preset,
        spec,
        graffiti=(args.graffiti or "").encode()[:32],
        graffiti_file=getattr(args, "graffiti_file", None),
    )
    print(f"validator client: {count} validators, "
          f"{len(args.beacon_nodes)} beacon node(s)")
    if args.dry_run:
        return 0
    last_slot = -1
    try:
        while True:
            node = nodes.best()
            slot = int(node.syncing()["head_slot"])
            if slot != last_slot:
                vc.on_slot(slot + 1)
                last_slot = slot
            time.sleep(1)
    except KeyboardInterrupt:
        pass
    return 0


# --- account manager --------------------------------------------------------


def cmd_am(args):
    from .crypto.keystore import Wallet, Keystore

    if args.am_cmd == "wallet-create":
        w = Wallet.create(args.name, args.password)
        print(w.to_json())
    elif args.am_cmd == "wallet-recover":
        from .crypto.keystore import KeystoreError

        try:
            if args.seed is not None:
                seed = bytes.fromhex(args.seed.removeprefix("0x"))
            else:
                seed = None
            wordlist = None
            if args.wordlist:
                with open(args.wordlist) as f:
                    wordlist = f.read().split()
            # exactly-one-of is enforced by Wallet.recover itself
            w = Wallet.recover(
                args.name,
                args.password,
                mnemonic=args.mnemonic,
                seed=seed,
                wordlist=wordlist,
                passphrase=args.passphrase,
            )
        except (KeystoreError, ValueError, OSError) as e:
            raise SystemExit(f"wallet-recover: {e}") from None
        print(w.to_json())
    elif args.am_cmd == "validator-create":
        with open(args.wallet) as f:
            w = Wallet.from_json(f.read())
        ks = w.next_validator(args.password, args.keystore_password)
        with open(args.wallet, "w") as f:
            f.write(w.to_json())
        print(ks.to_json())
    elif args.am_cmd == "slashing-protection-export":
        from .validator_client.slashing_protection import SlashingDatabase

        db = SlashingDatabase(args.db)
        print(db.export_json(bytes.fromhex(args.genesis_validators_root)))
    elif args.am_cmd == "slashing-protection-import":
        from .validator_client.slashing_protection import SlashingDatabase

        db = SlashingDatabase(args.db)
        db.import_json(
            sys.stdin.read(),
            bytes.fromhex(args.genesis_validators_root),
        )
        print("imported")
    return 0


# --- database manager (reference database_manager/src/lib.rs) --------------


def cmd_db(args):
    import os

    from .store.kv import Column, FileStore

    if args.db_cmd in ("fsck", "prune-payloads", "compact"):
        # these WRITE (fsck included: opening runs journal recovery) —
        # refuse to race a live node on the same datadir
        args._datadir_lock = _lock_datadir(args.datadir)
    native_path = os.path.join(args.datadir, "chain.db")
    if os.path.isfile(native_path):
        from .store.native_kv import NativeStore

        kv = NativeStore(native_path)
    else:
        kv = FileStore(args.datadir)
    if args.db_cmd == "inspect":
        import struct

        from .store.kv import JOURNAL_KEY
        from .store.metadata import get_schema_version

        counts = {}
        for name in (
            "BLOCK", "STATE", "STATE_SUMMARY", "CHAIN", "FREEZER_BLOCK",
            "FREEZER_STATE", "FREEZER_BLOCK_ROOTS", "FREEZER_STATE_ROOTS",
        ):
            counts[name.lower()] = len(kv.keys(getattr(Column, name)))
        split = kv.get(Column.CHAIN, b"split_slot")
        print(json.dumps({
            "columns": counts,
            "schema_version": get_schema_version(kv),
            "split_slot": struct.unpack(">Q", split)[0] if split else 0,
            "journal_pending": kv.get(Column.JOURNAL, JOURNAL_KEY)
            is not None,
        }, indent=1))
    elif args.db_cmd == "fsck":
        from .store.fsck import run_fsck
        from .store.hot_cold import HotColdDB

        preset, spec = _spec_preset(args)
        # opening IS the recovery path: an interrupted batch replays or
        # rolls back here, then the invariant walk checks what is left.
        # --slots-per-restore-point matters for databases written before
        # the stride was persisted (fsck prefers the stored value when
        # present): checking a custom-stride datadir at the default
        # stride would report spurious missing restore points.
        db = HotColdDB(
            kv, preset, spec,
            slots_per_restore_point=args.slots_per_restore_point,
        )
        issues = run_fsck(db)
        print(json.dumps({
            "journal_recovery": db.journal_recovery,
            "clean": not issues,
            "issues": [str(i) for i in issues],
        }, indent=1))
        return 0 if not issues else 1
    elif args.db_cmd == "compact":
        if not hasattr(kv, "compact"):
            print("compact: not supported for this datadir format")
            return 1
        kv.compact()
        print("compacted")
    elif args.db_cmd == "prune-payloads":
        from .store.hot_cold import HotColdDB

        preset, spec = _spec_preset(args)
        db = HotColdDB(kv, preset, spec)
        n = db.prune_payloads()
        print(json.dumps({"pruned_payloads": n}))
    elif args.db_cmd == "version":
        from .store.metadata import CURRENT_SCHEMA_VERSION, get_schema_version

        on_disk = get_schema_version(kv)
        print(json.dumps({
            "on_disk": on_disk,
            "current": CURRENT_SCHEMA_VERSION,
        }))
    return 0


# --- tracing (utils/tracing.py export seat) --------------------------------


def cmd_trace(args):
    """Dump a Chrome trace-event JSON (Perfetto-loadable): from a running
    node's /lighthouse/tracing/dump when --url is given, else from a
    seeded in-process demo workload driven through the full gossip ->
    processor -> pipeline hot path."""
    from .utils import tracing

    if args.url:
        import urllib.request

        with urllib.request.urlopen(
            args.url.rstrip("/") + "/lighthouse/tracing/dump", timeout=15
        ) as r:
            body = r.read().decode()
        trace = json.loads(body)  # refuse to write a non-JSON artifact
        with open(args.out, "w") as f:
            f.write(body)
        print(json.dumps({
            "source": args.url,
            "events": len(trace.get("traceEvents", [])),
            "path": args.out,
        }))
        return 0

    # demo mode: a deterministic two-node simulator run under the seeded
    # tracer -- same seed, same trace, byte for byte
    import random

    from .crypto.bls import set_backend
    from .network import Simulator

    preset, spec = _spec_preset(args)
    tracer = tracing.configure(
        rng=random.Random(args.seed),
        clock=tracing.StepClock(step=1e-6),
        capacity=args.capacity,
    )
    set_backend("fake")  # the demo traces scheduling, not pairings
    sim = Simulator(2, args.validators, preset, spec)
    for slot in range(1, args.slots + 1):
        sim.run_slot(slot)
    # run one unaggregated attestation over the subnets too: blocks carry
    # their attestations in-body, so without this the demo trace would
    # never show the gossip_attestation lane
    from .state_transition import clone_state, process_slots

    node0 = sim.nodes[0]
    head = node0.chain.head_state
    adv = process_slots(
        clone_state(head), head.slot + 1, preset, spec
    )
    att = sim.producer.make_unaggregated(adv, head.slot, 0, 0)
    node0.publish_attestation(att, subnet=0)
    sim.drain()
    with open(args.out, "w") as f:
        f.write(tracer.dump_json())
    status = tracer.status()
    print(json.dumps({
        "source": "demo",
        "slots": args.slots,
        "events": status["recorded"],
        "dropped": status["dropped"],
        "path": args.out,
    }))
    return 0


# --- launch ledger (obs/ledger.py export seat) ------------------------------


def cmd_ledger(args):
    """Dump (and optionally report) the launch-ledger flight recorder:
    from a running node's /lighthouse/ledger/dump when --url is given,
    else from a seeded in-process demo run with continuous batching ON,
    so the dump carries merged-launch records with lane mix, padding,
    and preemption facts. --report prints the occupancy / pad-waste /
    compile-tax table (the same renderer tools/ledger_report.py and the
    HTTP report route use)."""
    from .obs import ledger as launch_ledger

    if args.url:
        import urllib.request

        with urllib.request.urlopen(
            args.url.rstrip("/") + "/lighthouse/ledger/dump", timeout=15
        ) as r:
            body = r.read().decode()
        dump = json.loads(body)  # refuse to write a non-JSON artifact
        with open(args.out, "w") as f:
            f.write(body)
        if args.report:
            stats = launch_ledger.stats_from_records(
                dump.get("records", []), dropped=dump.get("dropped", 0)
            )
            print(launch_ledger.format_report(stats))
        print(json.dumps({
            "source": args.url,
            "records": len(dump.get("records", [])),
            "dropped": dump.get("dropped", 0),
            "path": args.out,
        }))
        return 0

    # demo mode: the `cli trace` simulator workload, run with the
    # continuous-batching scheduler engaged -- same seed, same ledger
    # dump, byte for byte
    import os
    import random

    from .crypto.bls import get_backend_name, set_backend
    from .crypto.bls import pipeline as bls_pipeline
    from .crypto.bls import scheduler as bls_scheduler
    from .network import Simulator
    from .utils import tracing

    preset, spec = _spec_preset(args)
    prior_backend = get_backend_name()
    prior_cb = os.environ.get("LIGHTHOUSE_TPU_CONT_BATCH")
    os.environ["LIGHTHOUSE_TPU_CONT_BATCH"] = "1"
    try:
        tracing.configure(
            rng=random.Random(args.seed),
            clock=tracing.StepClock(step=1e-6),
            capacity=65536,
        )
        led = launch_ledger.configure(capacity=args.capacity)
        # fresh pipeline + scheduler: batch ids / entry seqs restart, so
        # two demo runs with one seed dump identical bytes
        bls_pipeline.configure()
        bls_scheduler.configure()
        set_backend("fake")  # the demo records scheduling, not pairings
        sim = Simulator(2, args.validators, preset, spec)
        for slot in range(1, args.slots + 1):
            sim.run_slot(slot)
        sim.drain()
        bls_scheduler.default_scheduler().drain()
    finally:
        set_backend(prior_backend)
        if prior_cb is None:
            os.environ.pop("LIGHTHOUSE_TPU_CONT_BATCH", None)
        else:
            os.environ["LIGHTHOUSE_TPU_CONT_BATCH"] = prior_cb
    with open(args.out, "w") as f:
        f.write(led.dump_json())
    if args.report:
        print(led.report_text())
    status = led.status()
    print(json.dumps({
        "source": "demo",
        "slots": args.slots,
        "records": status["recorded"],
        "dropped": status["dropped"],
        "kinds": status["kinds"],
        "path": args.out,
    }))
    return 0


# --- scenario harness (harness/scenario.py) ---------------------------------


def cmd_scenario(args):
    """Run a named adversarial scenario (partitions, churn, equivocation
    storms, long non-finality, crash-recovery) on the in-process
    simulator: seeded, invariant-checked every slot, SLO-checked at the
    end; --replay proves bit-identical trace export across two runs."""
    from .harness.scenario import PLANS, assert_bit_identical_replay, run_scenario

    if args.list:
        for name in sorted(PLANS):
            print(name)
        return 0
    if args.name not in PLANS:
        raise SystemExit(
            f"unknown scenario {args.name!r}; --list shows the catalogue"
        )
    plan = PLANS[args.name](
        seed=args.seed, nodes=args.nodes, validators=args.validators
    )
    if args.replay:
        result, _second = assert_bit_identical_replay(plan)
        result.report["replay_bit_identical"] = True
    else:
        result = run_scenario(plan)
    if args.out:
        with open(args.out, "w") as f:
            f.write(result.trace)
        result.report["trace_path"] = args.out
    print(json.dumps(result.report, indent=1))
    return 0 if not result.report["slo"]["failures"] else 1


# --- dev tools (reference lcli/src/main.rs:54-610) -------------------------


def cmd_tools(args):
    preset, spec = _spec_preset(args)
    if args.tool_cmd == "skip-slots":
        from .state_transition import process_slots
        from .types import interop_genesis_state

        state = interop_genesis_state(args.validators, preset, spec)
        t0 = time.time()
        state = process_slots(state, args.slots, preset, spec)
        print(json.dumps({
            "slots": args.slots,
            "state_root": "0x" + state.tree_hash_root().hex(),
            "seconds": round(time.time() - t0, 3),
        }))
    elif args.tool_cmd == "transition-blocks":
        # state-transition timing over a harness-built chain
        from .crypto.bls import set_backend
        from .harness import StateHarness

        set_backend("fake")
        h = StateHarness(args.validators, preset, spec, sign=False)
        t0 = time.time()
        h.extend_chain(args.slots)
        print(json.dumps({
            "blocks": args.slots,
            "per_block_ms": round((time.time() - t0) / args.slots * 1e3, 2),
        }))
    elif args.tool_cmd == "pretty-ssz":
        from .types import types_for, block_classes_for

        t = types_for(preset)
        _, signed_cls, _ = block_classes_for(t, args.fork)
        with open(args.file, "rb") as f:
            obj = signed_cls.from_ssz_bytes(f.read())
        print(repr(obj))
    elif args.tool_cmd == "interop-genesis":
        # lcli interop-genesis: write a deterministic genesis state
        from .types import interop_genesis_state

        state = interop_genesis_state(
            args.validators, preset, spec,
            genesis_time=args.genesis_time or int(time.time()),
        )
        out = args.file or "genesis.ssz"
        with open(out, "wb") as f:
            f.write(state.as_ssz_bytes())
        print(json.dumps({
            "validators": args.validators,
            "genesis_time": state.genesis_time,
            "genesis_validators_root":
                "0x" + bytes(state.genesis_validators_root).hex(),
            "path": out,
        }))
    elif args.tool_cmd == "new-testnet":
        # lcli new-testnet: a testnet directory from real deposits
        # (initialize_beacon_state_from_eth1 over interop keys)
        import os

        from .eth1.deposit_tree import DepositDataTree
        from .state_transition.genesis import (
            initialize_beacon_state_from_eth1,
        )
        from .types import interop_keypair
        from .types.containers import DepositData
        from .crypto.bls import INFINITY_SIGNATURE

        datas = []
        tree = DepositDataTree()
        for i in range(args.validators):
            _, pk = interop_keypair(i)
            d = DepositData(
                pubkey=pk.to_bytes(),
                withdrawal_credentials=b"\x00" * 32,
                amount=spec.max_effective_balance,
                signature=INFINITY_SIGNATURE,
            )
            datas.append(d)
            tree.push(d)
        deposits = [
            tree.deposit(i, datas[i], i + 1)
            for i in range(len(datas))
        ]
        from .crypto.bls import set_backend

        set_backend("fake")  # interop deposits carry no possession proofs
        state = initialize_beacon_state_from_eth1(
            b"\x42" * 32,
            args.genesis_time or int(time.time()),
            deposits,
            preset,
            spec,
        )
        outdir = args.file or "testnet"
        os.makedirs(outdir, exist_ok=True)
        with open(os.path.join(outdir, "genesis.ssz"), "wb") as f:
            f.write(state.as_ssz_bytes())
        with open(os.path.join(outdir, "config.json"), "w") as f:
            json.dump({
                "config_name": spec.config_name,
                "preset": preset.name,
                "validators": args.validators,
                "genesis_time": state.genesis_time,
                "genesis_validators_root":
                    "0x" + bytes(state.genesis_validators_root).hex(),
            }, f, indent=1)
        print(json.dumps({"path": outdir, "validators": args.validators}))
    return 0


def cmd_warm(args):
    """Standalone AOT bucket warm-up (deploy step): arm the persistent
    compile cache and compile every verifier shape bucket into it, so
    the NEXT process (the node) starts fully warm -- zero
    tpu_compile_cache_misses_total during slots."""
    from .utils.compile_cache import arm as _arm_compile_cache

    _arm_compile_cache()
    from .crypto.bls.backends.jax_tpu import warm_compile

    buckets = None
    if args.bucket:
        buckets = []
        for spec in args.bucket:
            parts = tuple(int(v) for v in spec.split(","))
            if len(parts) != 3:
                print(f"bad --bucket {spec!r}: want n_b,k_b,m_b")
                return 2
            buckets.append(parts)

    report = warm_compile(buckets=buckets)
    compiled = sum(1 for row in report if row["compiled"])
    for row in report:
        name = "x".join(str(v) for v in row["bucket"])
        state = "compiled" if row["compiled"] else "warm"
        print(f"{name:>16}  {row['seconds']:8.3f}s  {state}")
    print(
        f"{len(report)} buckets ({compiled} compiled, "
        f"{len(report) - compiled} already warm)"
    )
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="lighthouse-tpu")
    sub = parser.add_subparsers(dest="cmd", required=True)

    bn = sub.add_parser("bn", help="run a beacon node")
    _add_network_args(bn)
    bn.add_argument("--datadir", default=None)
    bn.add_argument("--http-port", type=int, default=0)
    bn.add_argument("--interop-validators", type=int, default=64)
    bn.add_argument("--genesis-time", type=int, default=None)
    bn.add_argument("--genesis", default="interop",
                    choices=["interop", "resume", "checkpoint",
                             "checkpoint-url", "deposit-contract"],
                    help="genesis resolution (ClientGenesis equivalent; "
                         "deposit-contract waits for eth1 deposits)")
    bn.add_argument("--eth1-endpoint", default=None,
                    help="eth1 JSON-RPC URL: deposit polling + eth1-data "
                         "voting + deposit inclusion in produced blocks")
    bn.add_argument("--genesis-timeout", type=float, default=600.0,
                    help="deposit-contract genesis: seconds to wait for "
                         "a valid genesis before giving up")
    bn.add_argument("--checkpoint-sync-url", default=None,
                    help="trusted node URL for --genesis checkpoint-url")
    bn.add_argument("--checkpoint-state", default=None,
                    help="SSZ file: finalized BeaconState anchor")
    bn.add_argument("--checkpoint-block", default=None,
                    help="SSZ file: finalized SignedBeaconBlock anchor")
    bn.add_argument("--listen-port", type=int, default=None,
                    help="TCP wire listener port (0 = ephemeral)")
    bn.add_argument("--bootnode", default=None,
                    help="host:port of a bootnode registry to join")
    bn.add_argument("--peer-id", default=None)
    bn.add_argument("--monitoring-endpoint", default=None,
                    help="push process/system/chain health JSON here "
                    "(common/monitoring_api parity)")
    bn.add_argument("--dry-run", action="store_true")
    bn.add_argument("--warm-compile", action="store_true",
                    help="AOT-compile every verifier shape bucket before "
                         "serving (cli warm, inline): a fresh node never "
                         "JITs during a slot")
    bn.add_argument("--processor-workers", type=int, default=1,
                    help="gossip worker pool size (beacon_processor)")
    bn.add_argument("--serving-no-cache", action="store_true",
                    help="disable the anchored HTTP response cache")
    bn.add_argument("--serving-cache-entries", type=int, default=512,
                    help="response-cache LRU bound (entries)")
    bn.add_argument("--serving-max-subscribers", type=int, default=64,
                    help="concurrent live SSE subscriber cap")
    bn.add_argument("--serving-queue-wait-p95", type=float, default=0.5,
                    help="shed threshold: processor queue-wait p95 "
                         "seconds (debug lane sheds at 1x, read-only "
                         "at 2x)")
    bn.add_argument("--serving-slot-delay-p95", type=float, default=4.0,
                    help="shed threshold: block-import slot-delay p95 "
                         "seconds")
    bn.add_argument("--serving-retry-after", type=int, default=1,
                    help="Retry-After seconds on shed (503) responses")
    bn.add_argument("--speculate", action="store_true",
                    help="duty-driven precompute: committee aggregate "
                         "pubkeys built at each epoch boundary so "
                         "aggregate verification skips per-set pubkey "
                         "aggregation, plus idle-time next-slot "
                         "pre-verification (speculate/)")
    bn.add_argument("--speculate-queue-wait-p95", type=float, default=0.05,
                    help="idle gate: speculation only runs while the "
                         "processor queue-wait p95 stays under this "
                         "many seconds")
    bn.set_defaults(fn=cmd_bn)

    boot = sub.add_parser("boot-node", help="run a discovery bootnode")
    boot.add_argument("--port", type=int, default=0)

    def cmd_boot(args):
        from .network import Bootnode

        b = Bootnode(port=args.port).start()
        print(f"bootnode on {b.host}:{b.port}")
        try:
            while True:
                time.sleep(60)
        except KeyboardInterrupt:
            b.stop()
        return 0

    boot.set_defaults(fn=cmd_boot)

    vc = sub.add_parser("vc", help="run a validator client")
    _add_network_args(vc)
    vc.add_argument("--beacon-nodes", nargs="+",
                    default=["http://127.0.0.1:5052"])
    vc.add_argument("--interop-validators", default=None,
                    help="range lo..hi of interop keys")
    vc.add_argument("--keystores", nargs="*", default=None)
    vc.add_argument("--password", default=None)
    vc.add_argument("--graffiti", default=None,
                    help="default graffiti text for produced blocks")
    vc.add_argument("--graffiti-file", default=None,
                    help="per-validator graffiti: '0x<pubkey>: text' "
                         "lines, 'default: text' fallback")
    vc.add_argument("--dry-run", action="store_true")
    vc.set_defaults(fn=cmd_vc)

    am = sub.add_parser("am", help="account manager")
    am.add_argument("am_cmd", choices=[
        "wallet-create", "wallet-recover", "validator-create",
        "slashing-protection-export", "slashing-protection-import",
    ])
    am.add_argument("--mnemonic", default=None)
    am.add_argument("--seed", default=None)
    am.add_argument("--wordlist", default=None, help="BIP-39 wordlist file")
    am.add_argument(
        "--passphrase", default="",
        help="BIP-39 passphrase the seed was derived with",
    )
    am.add_argument("--name", default="wallet")
    am.add_argument("--password", default="")
    am.add_argument("--keystore-password", default="")
    am.add_argument("--wallet", default=None)
    am.add_argument("--db", default=":memory:")
    am.add_argument("--genesis-validators-root", default="00" * 32)
    am.set_defaults(fn=cmd_am)

    db = sub.add_parser("db", help="database manager")
    _add_network_args(db)
    db.add_argument(
        "db_cmd",
        choices=["inspect", "fsck", "compact", "version", "prune-payloads"],
    )
    db.add_argument("--datadir", required=True)
    db.add_argument(
        "--slots-per-restore-point", type=int, default=None,
        help="stride the node ran with (fsck fallback for databases "
        "written before the stride was persisted in the chain column)",
    )
    db.set_defaults(fn=cmd_db)

    trace = sub.add_parser(
        "trace", help="dump a Chrome/Perfetto trace from a node or a demo run"
    )
    _add_network_args(trace)
    trace.add_argument("--url", default=None,
                       help="running node base URL; fetches its "
                            "/lighthouse/tracing/dump ring")
    trace.add_argument("--out", default="trace.json")
    trace.add_argument("--slots", type=int, default=4,
                       help="demo mode: slots of simulated network to trace")
    trace.add_argument("--validators", type=int, default=16)
    trace.add_argument("--seed", type=int, default=0)
    trace.add_argument("--capacity", type=int, default=65536,
                       help="span ring size for the demo tracer")
    trace.set_defaults(fn=cmd_trace)

    ledger = sub.add_parser(
        "ledger",
        help="dump/report the launch-ledger flight recorder from a node "
             "or a seeded demo run",
    )
    _add_network_args(ledger)
    ledger.add_argument("--url", default=None,
                        help="running node base URL; fetches its "
                             "/lighthouse/ledger/dump ring")
    ledger.add_argument("--out", default="ledger.json")
    ledger.add_argument("--report", action="store_true",
                        help="print the occupancy/pad-waste/compile-tax "
                             "table")
    ledger.add_argument("--slots", type=int, default=4,
                        help="demo mode: slots of simulated network")
    ledger.add_argument("--validators", type=int, default=16)
    ledger.add_argument("--seed", type=int, default=0)
    ledger.add_argument("--capacity", type=int, default=4096,
                        help="launch ring size for the demo ledger")
    ledger.set_defaults(fn=cmd_ledger)

    scen = sub.add_parser(
        "scenario",
        help="run a deterministic adversarial scenario on the simulator",
    )
    _add_network_args(scen)
    scen.add_argument("--name", default="partition",
                      help="scenario family (--list shows the catalogue)")
    scen.add_argument("--list", action="store_true",
                      help="list the scenario catalogue and exit")
    scen.add_argument("--seed", type=int, default=0)
    scen.add_argument("--nodes", type=int, default=4)
    scen.add_argument("--validators", type=int, default=64)
    scen.add_argument("--replay", action="store_true",
                      help="run twice and assert bit-identical trace "
                           "export + final heads")
    scen.add_argument("--out", default=None,
                      help="write the Chrome trace-event JSON here")
    scen.set_defaults(fn=cmd_scenario)

    warm = sub.add_parser(
        "warm",
        help="AOT-compile every verifier shape bucket into the "
             "persistent compile cache (deploy-time warm pass)",
    )
    warm.add_argument("--bucket", action="append", default=None,
                      metavar="N,K,M",
                      help="bucketed (sets, pubkeys, messages) shape to "
                           "warm; repeatable; default is the built-in "
                           "steady-state set")
    warm.set_defaults(fn=cmd_warm)

    tools = sub.add_parser("tools", help="dev tools (lcli)")
    _add_network_args(tools)
    tools.add_argument("tool_cmd", choices=[
        "skip-slots", "transition-blocks", "pretty-ssz",
        "interop-genesis", "new-testnet",
    ])
    tools.add_argument("--validators", type=int, default=64)
    tools.add_argument("--slots", type=int, default=8)
    tools.add_argument("--fork", default="phase0")
    tools.add_argument("--file", default=None)
    tools.add_argument("--genesis-time", type=int, default=None)
    tools.set_defaults(fn=cmd_tools)

    args = parser.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
