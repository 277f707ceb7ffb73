"""Server CLI: `python -m minio_tpu.server ENDPOINT... [options]`.

Equivalent of `minio server` (cmd/server-main.go:422).  Endpoints are
drive dirs or `{1...N}` ellipses patterns; with `http://host:port/path`
endpoints the node boots in distributed mode, serving its local drives to
peers over the storage RPC plane and locking via dsync:

    # single node, 8 drives
    python -m minio_tpu.server /data/d{1...8}

    # 2 nodes x 4 drives, one pool (run on each host with the same args)
    python -m minio_tpu.server --address 0.0.0.0:9000 \\
        http://node{1...2}:9000/data/d{1...4}

Multiple ellipses arguments define multiple server pools (reference
cmd/endpoint-ellipses.go:341 — each arg is a pool; placement picks a
pool by available space, reads/listing/deletes span all pools):

    # expand an existing deployment with a second pool
    python -m minio_tpu.server /data/pool1/d{1...8} /data/pool2/d{1...8}
"""

from __future__ import annotations

import argparse
import os
import sys


def _prefork_http_front(n: int, argv) -> int:
    """MINIO_TPU_HTTP_WORKERS=N pre-fork front (ISSUE 8): fork N server
    processes that all bind the SAME address via SO_REUSEPORT, so
    accept + HTTP parse + SigV4 verification + response streaming
    parallelize across interpreters (the kernel load-balances new
    connections).  Worker 0 runs the background services; the rest
    start with --no-services so one node never runs N scanners.
    Worker 0 also owns the chip — a TPU belongs to one process — so the
    rest are pinned to the host codec before they import anything.
    Children are supervised: a died worker is reforked, SIGTERM/SIGINT
    fan out and the parent waits for a clean drain.

    Caveat (documented in README): the per-object namespace write lock
    is per-process, so two workers racing a PUT of the SAME key
    serialize only at the atomic commit rename (last-writer-wins —
    the same semantics two distinct NODES have without dsync).  The
    pre-fork front targets read-heavy / many-client fan-in; use
    distributed mode when cross-writer locking matters."""
    import signal

    def spawn(i: int) -> int:
        pid = os.fork()
        if pid == 0:
            # a REFORKED child inherits the supervisor's on_sig handler
            # (installed below before any refork) — reset to default or
            # a SIGTERM landing during the child's boot window would be
            # swallowed by the supervisor handler and the child would
            # survive its own shutdown, wedging the parent's final wait
            signal.signal(signal.SIGTERM, signal.SIG_DFL)
            signal.signal(signal.SIGINT, signal.SIG_DFL)
            os.environ["_MINIO_TPU_HTTP_WORKER"] = str(i)
            if i > 0:
                os.environ["MINIO_TPU_ERASURE_BACKEND"] = "host"
            child_argv = list(argv) if argv is not None else sys.argv[1:]
            if i > 0 and "--no-services" not in child_argv:
                child_argv = child_argv + ["--no-services"]
            os._exit(main(child_argv))
        return pid

    live = {i: spawn(i) for i in range(n)}
    print(f"minio-tpu: pre-fork HTTP front, {n} workers "
          f"(SO_REUSEPORT)", file=sys.stderr)
    stopping: list[int] = []

    def on_sig(sig, _frame):
        stopping.append(sig)
        for pid in live.values():
            try:
                os.kill(pid, signal.SIGTERM)
            except OSError:
                pass

    signal.signal(signal.SIGTERM, on_sig)
    signal.signal(signal.SIGINT, on_sig)
    while live and not stopping:
        try:
            pid, _status = os.wait()
        except ChildProcessError:
            break
        except InterruptedError:
            continue
        for i, p in list(live.items()):
            if p == pid:
                del live[i]
                if not stopping:
                    live[i] = spawn(i)  # supervised: refork
    for pid in live.values():
        try:
            os.waitpid(pid, 0)
        except OSError:
            pass
    return 0


def _count_compile_seconds() -> None:
    """Book every XLA compilation of this process under the data plane's
    stage `compile` (`minio_dataplane_stage_seconds_total{stage=
    "compile"}`): a batch shape that compiles inside a request is
    seconds of a PUT an operator could not otherwise see.  JAX reports
    the backend's compile, or the persistent cache's answer in its
    place, per program, on the thread that compiled: outside a device
    self-test (boot's, the warm-up thread's) that thread is a
    request's, and the stage `compile_wait` takes the seconds too."""
    import jax.monitoring

    from minio_tpu.erasure import stagestats
    from minio_tpu.erasure.coding import _DeviceCodec

    def fold(event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            stagestats.add("compile", seconds)
            if not _DeviceCodec.self_testing():
                stagestats.add("compile_wait", seconds)

    jax.monitoring.register_event_duration_secs_listener(fold)


def _init_device(backend: str):
    """Initialise JAX for a backend that may use a device and say what
    it found (ops/device.DeviceInfo); None for backend "host", which
    never touches JAX.  Raises BackendUnavailable when the backend names
    a device that is not there — the server refuses to boot instead of
    serving from the host codec under a device backend's name."""
    from minio_tpu.ops import device

    if backend == "host":
        return None
    device.enable_compile_cache()
    _count_compile_seconds()
    if backend == "tpu":
        return device.require_tpu("MINIO_TPU_ERASURE_BACKEND=tpu")
    return device.info()


def _boot_erasure_plane(pools) -> dict:
    """Resolve, per geometry a healthy set writes (STANDARD and
    REDUCED_REDUNDANCY parity of every pool), where steady-state batches
    are coded, and run the device self-test + warm-up for each geometry
    that reaches the chip, here, before the node serves.  A geometry
    that only a set with drives away writes (the upgraded parity of a
    degraded PUT: seven more on sixteen drives) is warmed when the set
    first sees drives missing, on a background thread, and coded by the
    host codec until then (coding._DeviceCodec.ready).  Returns
    {"geometry": {"k+m": where}, "batchSizes": the block counts the
    device programs are compiled at (what any dispatch of the single
    chip is carried at), "deviceSelfTestSeconds": float} for the banner
    and admin info, which adds the geometries warmed later."""
    from minio_tpu.erasure import coding
    from minio_tpu.erasure.objects import PutObjectOptions

    geoms = set()
    for pool in pools.pools:
        for es in pool.sets:
            for sc in ("STANDARD", "REDUCED_REDUNDANCY"):
                parity = es._parity_for(PutObjectOptions(storage_class=sc))
                if parity:
                    geoms.add((len(es.disks) - parity, parity))
    where, seconds = {}, 0.0
    for k, m in sorted(geoms):
        where[f"{k}+{m}"] = coding.steady_state_backend(k, m)
        if where[f"{k}+{m}"] == "device":
            seconds += coding._DeviceCodec.self_test(k, m)
    return {"geometry": where,
            "batchSizes": list(coding.DEVICE_BATCH_SIZES),
            "deviceSelfTestSeconds": round(seconds, 3)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="minio-tpu server")
    ap.add_argument("endpoints", nargs="+",
                    help="drive dirs / URLs, ellipses like /data/d{1...8}")
    ap.add_argument("--address", default="127.0.0.1:9000")
    ap.add_argument("--access-key",
                    default=os.environ.get("MINIO_ROOT_USER", "minioadmin"))
    ap.add_argument("--secret-key",
                    default=os.environ.get("MINIO_ROOT_PASSWORD", "minioadmin"))
    ap.add_argument("--region", default="us-east-1")
    ap.add_argument("--set-size", type=int, default=None)
    ap.add_argument("--scan-interval", type=float,
                    default=float(os.environ.get(
                        "MINIO_TPU_SCAN_INTERVAL", "60")))
    ap.add_argument("--heal-interval", type=float,
                    default=float(os.environ.get(
                        "MINIO_TPU_HEAL_INTERVAL", "3600")))
    ap.add_argument("--no-services", action="store_true",
                    help="do not start heal/MRF/scanner background services")
    ap.add_argument("--gateway", choices=["s3", "nas"], default=None,
                    help="gateway mode: 's3' proxies objects to a remote "
                         "backend (endpoints arg = backend URL, plus "
                         "--gateway-metadata-dir for local IAM/config "
                         "state); 'nas' serves a shared filesystem mount "
                         "as the object store (endpoints arg = the NAS "
                         "path, reference cmd/gateway/nas)")
    ap.add_argument("--gateway-metadata-dir", default="./gateway-meta",
                    help="local directory for gateway IAM/config state")
    ap.add_argument("--gateway-access-key",
                    default=os.environ.get("MINIO_GATEWAY_ACCESS_KEY", ""))
    ap.add_argument("--gateway-secret-key",
                    default=os.environ.get("MINIO_GATEWAY_SECRET_KEY", ""))
    ap.add_argument("--cache-dir",
                    default=os.environ.get("MINIO_CACHE_DIR", ""),
                    help="local read-cache directory (SSD cache for "
                         "GETs in server AND gateway mode, reference "
                         "cmd/disk-cache.go)")
    ap.add_argument("--cache-size", type=int,
                    default=int(os.environ.get(
                        "MINIO_CACHE_SIZE", str(10 << 30))),
                    help="max cache bytes (default 10 GiB)")
    args = ap.parse_args(argv)

    # optional pre-fork/SO_REUSEPORT HTTP front: fork BEFORE any heavy
    # import so each worker boots a clean interpreter
    try:
        http_workers = int(os.environ.get(
            "MINIO_TPU_HTTP_WORKERS", "1") or 1)
    except ValueError:
        http_workers = 1
    import socket as _socket

    if (http_workers > 1 and args.gateway is None
            and hasattr(_socket, "SO_REUSEPORT")
            and "_MINIO_TPU_HTTP_WORKER" not in os.environ):
        return _prefork_http_front(http_workers, argv)

    from aiohttp import web

    import time

    from minio_tpu.distributed.node import ClusterNode
    from minio_tpu.ops import host as host_codec
    from minio_tpu.ops.device import BackendUnavailable
    from minio_tpu.selftest import SelfTestError, run_self_tests

    # refuse to serve IO with a broken codec/hash (reference
    # erasureSelfTest/bitrotSelfTest fatal at boot), and refuse a device
    # backend whose device is not there
    backend = os.environ.get("MINIO_TPU_ERASURE_BACKEND", "auto")
    try:
        t0 = time.perf_counter()
        run_self_tests()
        t1 = time.perf_counter()
        dev = _init_device(backend) if args.gateway is None else None
        boot = {"hostSelfTestSeconds": round(t1 - t0, 3),
                "jaxInitSeconds": round(time.perf_counter() - t1, 3)}
    except (SelfTestError, BackendUnavailable) as e:
        print(f"minio-tpu: FATAL: {e}", file=sys.stderr)
        return 1

    if args.gateway == "nas":
        # `python -m minio_tpu.server --gateway nas /mnt/nas`
        # (reference `minio gateway nas PATH`, cmd/gateway/nas/
        # gateway-nas.go) — a filesystem-backed ObjectLayer: the
        # single-drive erasure layer at k=1,m=0 over the NAS mount, so
        # objects live as plain shard files + metadata on the share
        from minio_tpu.erasure.sets import ErasureServerPools, ErasureSets
        from minio_tpu.server.app import make_app
        from minio_tpu.storage.local import LocalStorage

        if len(args.endpoints) != 1:
            print("minio-tpu: nas gateway takes exactly one path",
                  file=sys.stderr)
            return 1
        pools_layer = ErasureServerPools([
            ErasureSets([LocalStorage(args.endpoints[0])], set_size=1)])
        layer = pools_layer
        if args.cache_dir:
            from minio_tpu.gateway.cache import CacheLayer

            layer = CacheLayer(pools_layer, args.cache_dir,
                               max_size=args.cache_size)
        # background services run on the INNER erasure layer — their
        # scans must not churn the SSD cache (same split as ClusterNode)
        app = make_app(layer, start_services=False,
                       access_key=args.access_key,
                       secret_key=args.secret_key, region=args.region)
        if not args.no_services:
            from minio_tpu.server.app import S3_SERVER_KEY
            from minio_tpu.services import ServiceManager

            app[S3_SERVER_KEY].attach_services(ServiceManager(
                pools_layer, scan_interval=args.scan_interval,
                heal_interval=args.heal_interval))
        host, _, port = args.address.partition(":")
        print(f"minio-tpu: gateway/nas -> {args.endpoints[0]}, "
              f"S3 on http://{args.address}", file=sys.stderr)
        web.run_app(app, host=host or "0.0.0.0",
                    port=int(port or 9000), print=None)
        return 0

    if args.gateway == "s3":
        # `python -m minio_tpu.server --gateway s3 https://backend`
        # (reference `minio gateway s3 ...`, cmd/gateway-main.go)
        from minio_tpu.gateway import S3Gateway
        from minio_tpu.server.app import make_app

        if len(args.endpoints) != 1:
            print("minio-tpu: gateway mode takes exactly one backend URL",
                  file=sys.stderr)
            return 1
        layer = S3Gateway(
            args.endpoints[0],
            args.gateway_access_key or args.access_key,
            args.gateway_secret_key or args.secret_key,
            metadata_dir=args.gateway_metadata_dir, region=args.region)
        if args.cache_dir:
            from minio_tpu.gateway.cache import CacheLayer

            layer = CacheLayer(layer, args.cache_dir,
                               max_size=args.cache_size)
        app = make_app(layer, start_services=False,
                       access_key=args.access_key,
                       secret_key=args.secret_key, region=args.region)
        host, _, port = args.address.partition(":")
        print(f"minio-tpu: gateway/s3 -> {args.endpoints[0]}, "
              f"S3 on http://{args.address}", file=sys.stderr)
        web.run_app(app, host=host or "0.0.0.0",
                    port=int(port or 9000), print=None)
        return 0

    node = ClusterNode(
        args.endpoints, my_address=args.address,
        access_key=args.access_key, secret_key=args.secret_key,
        region=args.region, set_size=args.set_size,
        start_services=not args.no_services,
        scan_interval=args.scan_interval,
        heal_interval=args.heal_interval,
        cache_dir=args.cache_dir, cache_size=args.cache_size,
    )
    pools_info = node.pools.storage_info()["pools"]
    mode = "distributed" if node.distributed else "standalone"
    layout = " + ".join(
        f"{i['sets']}x{i['drives_per_set']}" for i in pools_info)
    print(
        f"minio-tpu: {mode}, {len(node.local_drives)} local drives, "
        f"{len(pools_info)} pool(s) [{layout} drives], "
        f"S3 on http://{args.address}", file=sys.stderr,
    )
    try:
        boot.update(_boot_erasure_plane(node.pools))
    except (SelfTestError, BackendUnavailable) as e:
        print(f"minio-tpu: FATAL: {e}", file=sys.stderr)
        node.close()
        return 1
    node.s3.erasure_boot = boot
    geometry = ", ".join(f"{g} -> {w}" for g, w in boot["geometry"].items())
    if dev is not None:
        on = f"{dev.count} x {dev.platform} ({dev.kind})"
    elif os.environ.get("_MINIO_TPU_HTTP_WORKER", "0") != "0":
        on = "no device (HTTP worker 0 owns the chip)"
    else:
        on = "no device (JAX not initialised)"
    print(
        f"minio-tpu: erasure backend {backend} on {on}: {geometry}; "
        f"host codec "
        f"{'native AVX2' if host_codec.available() else 'numpy fallback'}; "
        f"device self-test + warm-up {boot['deviceSelfTestSeconds']} s "
        f"at batches of {boot['batchSizes']} blocks (the geometries a "
        f"healthy set writes; any other, a degraded PUT's raised parity, "
        f"is warmed in the background when a set first misses drives and "
        f"coded on the host until then)",
        file=sys.stderr,
    )
    if node.distributed:
        # peers may still be starting: retry bootstrap verification in the
        # background for a bounded window (waitForFormatErasure analogue)
        import time as _time

        from minio_tpu.utils.deadline import service_thread

        def verify_with_retry():
            for _ in range(30):
                problems = node.verify_cluster()
                if not problems:
                    print("minio-tpu: cluster bootstrap verified",
                          file=sys.stderr)
                    return
                _time.sleep(1)
            for p in problems:
                print(f"minio-tpu: bootstrap warning: {p}", file=sys.stderr)

        service_thread(verify_with_retry, name="bootstrap-verify")

    host, port = args.address.rsplit(":", 1)
    reuse_port = "_MINIO_TPU_HTTP_WORKER" in os.environ or None
    try:
        web.run_app(node.app, host=host, port=int(port), print=None,
                    reuse_port=reuse_port)
    finally:
        node.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
