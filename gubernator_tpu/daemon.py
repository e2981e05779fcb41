"""Daemon — process assembly (reference daemon.go).

Builds the mesh store + metrics + V1Service, serves the HTTP/JSON
gateway (client API, peer data plane, /metrics), wires peer discovery,
and handles graceful shutdown with Loader save.  `set_peers` stamps
IsOwner by advertise-address compare exactly like daemon.go:277-287.
"""

from __future__ import annotations

import socket
import threading
import time
from typing import List, Optional, Sequence

from .config import DaemonConfig
from .gateway import GatewayServer
from .grpc_server import GrpcServer, channel_credentials
from .tls import setup_tls
from .metrics import Metrics
from .service import ServiceConfig, V1Service
from .types import PeerInfo
from .utils.clock import Clock, DEFAULT_CLOCK
from .utils.net import resolve_host_ip


class Daemon:
    def __init__(self, conf: DaemonConfig, clock: Optional[Clock] = None):
        self.conf = conf
        self.clock = clock or DEFAULT_CLOCK
        self.service: Optional[V1Service] = None
        self.gateway: Optional[GatewayServer] = None
        self.grpc: Optional[GrpcServer] = None
        self._pool = None
        self._closed = False

    # ------------------------------------------------------------------
    def start(self) -> "Daemon":
        """daemon.go:72-251.  On any startup failure, tear down whatever
        was already running — a half-started daemon must not leak bound
        ports and service threads to a retrying supervisor."""
        try:
            return self._start()
        except BaseException:
            self.close()
            raise

    def _start(self) -> "Daemon":
        # Tracing is process-wide (per-thread contexts, one flight
        # recorder); the daemon's parsed GUBER_TRACE_SAMPLE wins over
        # the module's import-time env default — unconditionally, so a
        # config that says 0 also DISABLES tracing a stale environment
        # variable turned on.
        from . import blackbox, profiling, telemetry, tracing

        tracing.set_sample_rate(self.conf.behaviors.trace_sample)
        # The incident black box's master switch is process-wide like
        # tracing; the parsed GUBER_BLACKBOX wins over the module's
        # import-time env default, in both directions.  (The rings,
        # bundle dir and budgets are per-service — V1Service builds
        # them from the behaviors below.)
        blackbox.set_enabled(self.conf.behaviors.blackbox)
        # XLA telemetry is process-wide like tracing; the parsed
        # GUBER_XLA_TELEMETRY wins over the module's import-time env
        # default, in both directions.
        telemetry.set_enabled(self.conf.behaviors.xla_telemetry)
        telemetry.set_storm(
            self.conf.behaviors.xla_storm,
            self.conf.behaviors.xla_storm_window_s,
        )
        # The continuous host profiler is process-wide like tracing;
        # the parsed GUBER_PROFILE/GUBER_PROFILE_HZ win over the
        # module's import-time env defaults, in both directions (the
        # sampler thread starts on first enable and idles at one
        # branch per tick when disabled).
        profiling.set_hz(self.conf.behaviors.profile_hz)
        profiling.set_enabled(self.conf.behaviors.profile)
        # Everything compiled from here to the end of startup warmup is
        # warmup by definition; after mark_steady() below any further
        # backend compile counts as a steady-state recompile (shape
        # churn) and can trip the recompile-storm dump.
        telemetry.begin_warmup()
        tls_conf = setup_tls(self.conf.tls)
        server_tls = tls_conf.server_ctx if tls_conf else None
        # Peer data plane credentials: gRPC channel creds unless the
        # config demands skipped verification, which only the ssl-context
        # HTTP fallback honors (PeerClient picks the transport).
        peer_creds = None
        if tls_conf is not None and not tls_conf.insecure_skip_verify:
            peer_creds = channel_credentials(tls_conf)
        metrics = Metrics()
        svc_conf = ServiceConfig(
            cache_size=self.conf.cache_size,
            back_cache_size=self.conf.back_cache_size,
            global_cache_size=self.conf.global_cache_size,
            behaviors=self.conf.behaviors,
            data_center=self.conf.data_center,
            persist_store=self.conf.store,
            loader=self.conf.loader,
            snapshot_path=getattr(self.conf, "snapshot_path", ""),
            blackbox_dir=getattr(self.conf, "blackbox_dir", ""),
            clock=self.clock,
            metrics=metrics,
            devices=self.conf.devices,
            peer_tls_context=tls_conf.client_ctx if tls_conf else None,
            peer_channel_credentials=peer_creds,
            fault_plan=self.conf.fault_plan,
        )
        import jax

        with telemetry.startup("backend"):
            jax.devices()  # the backend comes up here, whoever asks first
        with telemetry.startup("table"):
            self.service = V1Service(svc_conf)
        # Compile the device programs BEFORE accepting traffic: a cold
        # first dispatch (an XLA compile: seconds on a CPU, most of a
        # minute per program on a TPU) would otherwise land inside a
        # client's RPC deadline.  The GLOBAL manager, ticking since the
        # service was built, is held off meanwhile: the pass that syncs
        # warm-up's own GLOBAL key loads the sync program and must be
        # warm-up's, not a tick's (see GlobalManager.tick_lock).
        with telemetry.startup("warmup"), self.service.global_mgr.tick_lock:
            self.service.store.warmup(
                self.clock.now_ms(), warm_shapes=self.conf.warmup_shapes
            )
        telemetry.mark_steady()
        with telemetry.startup("listen"):
            self._listen(tls_conf, server_tls)
        return self

    def _listen(self, tls_conf, server_tls) -> None:
        """Bring up the gRPC server, the HTTP edge and peer discovery,
        and wait until every listener accepts."""
        grpc_listen = self.conf.grpc_listen_address
        if not grpc_listen:
            host, _, _ = self.conf.listen_address.partition(":")
            grpc_listen = f"{host or '127.0.0.1'}:0"
        self.grpc = GrpcServer(
            self.service, grpc_listen, tls_conf=tls_conf,
            max_conn_age_s=getattr(self.conf, "grpc_max_conn_age_s", 0),
        ).start()
        # HTTP edge selection: the C++ epoll edge (NativeGatewayServer)
        # wins tail latency and per-request overhead, but on a 1-core
        # host the stdlib gateway's unbounded blocked threads keep more
        # device windows in flight and win bulk-batch throughput.
        # Default is therefore the stdlib gateway;
        # GUBER_NATIVE_HTTP=1 / native_http=True opts into the native
        # edge (latency-sensitive or many-core deployments).  TLS always
        # uses the Python+ssl gateway.
        self.gateway = None
        if self.conf.native_http is True and server_tls is not None:
            raise RuntimeError(
                "GUBER_NATIVE_HTTP=1 is incompatible with TLS: the native "
                "edge has no TLS support (use the default stdlib gateway)"
            )
        if server_tls is None and self.conf.native_http is True:
            from . import native as _native
            from .gateway import NativeGatewayServer

            if not _native.available():
                raise RuntimeError(
                    f"GUBER_NATIVE_HTTP=1 but native runtime unavailable: "
                    f"{_native.build_error()}"
                )
            self.gateway = NativeGatewayServer(
                self.service, self.conf.listen_address,
                n_workers=self.conf.native_workers,
                acceptors=getattr(self.conf, "acceptors", 1),
                uds_path=getattr(self.conf, "uds_path", ""),
            )
            # Native ingress service loop (architecture.md "Native
            # service loop"): steady-state kind-5 frames run GIL-free
            # from socket to device pipeline, Python at batch
            # granularity only.  GUBER_NATIVE_INGRESS=0 = the PR 8
            # edge, behavior-identical (the interop/A-B off switch).
            if (
                self.conf.behaviors.native_ingress
                and self.service.serves_ingress_columns
            ):
                from .gateway import NativeIngressPump

                pump = NativeIngressPump(self.service).start()
                pump.update_ring()
                self.gateway.pump = pump
        if self.gateway is None:
            self.gateway = GatewayServer(
                self.service, self.conf.listen_address, tls_context=server_tls
            )
        self.gateway.start()
        # Port 0 resolves at bind time; a wildcard host — bound OR
        # explicitly configured — must be replaced by a routable IP
        # before peers see it (net.go:12-33 via config.go:249).  The
        # advertise address names the gRPC data plane (config.go:249).
        self.service.conf.advertise_address = resolve_host_ip(
            self.conf.advertise_address or self.grpc.address
        )
        self.http_advertise = resolve_host_ip(self.gateway.address)

        if self.conf.peer_discovery_type == "static":
            # A static daemon with no peer list serves standalone: it is
            # its own (sole) owner for every key.
            self.set_peers(self.conf.peers or [self.peer_info])
        elif self.conf.peer_discovery_type == "file":
            from .peers import FilePool

            self._pool = FilePool(self.conf.peers_file, on_update=self.set_peers)
        elif self.conf.peer_discovery_type in ("etcd", "member-list", "k8s"):
            from .peers import make_pool

            self._pool = make_pool(
                self.conf.peer_discovery_type,
                self.conf,
                on_update=self.set_peers,
                advertise=self.peer_info,
            )
        self.wait_for_connect()

    # ------------------------------------------------------------------
    @property
    def peer_info(self) -> PeerInfo:
        return PeerInfo(
            grpc_address=self.service.conf.advertise_address,
            http_address=self.http_advertise,
            data_center=self.conf.data_center,
        )

    def set_peers(self, peers: Sequence[PeerInfo]) -> None:
        """Stamp IsOwner by address compare, then hand to the service
        (daemon.go:277-287).  Both of this daemon's addresses count as
        "me": a static peer list naming only the HTTP address (the
        reference's lists name gRPC addresses, but a gateway-only config
        is legal here) must still self-identify.

        Late updates after close() are dropped: a discovery poller
        thread racing shutdown must not rebuild pickers (or trigger a
        resharding handoff) against a half-torn-down service."""
        if self._closed or self.service is None:
            return
        mine = {self.service.conf.advertise_address, self.http_advertise}
        stamped = []
        for p in peers:
            q = PeerInfo(
                grpc_address=p.grpc_address,
                http_address=p.http_address or p.grpc_address,
                data_center=p.data_center,
                is_owner=(p.grpc_address in mine or p.http_address in mine),
            )
            stamped.append(q)
        self.service.set_peers(stamped)

    # ------------------------------------------------------------------
    def wait_for_connect(self, timeout_s: float = 10.0) -> None:
        """Block until every listener accepts (daemon.go:305-344)."""
        deadline = time.monotonic() + timeout_s
        for address in (self.gateway.address, self.grpc.address):
            host, _, port = address.partition(":")
            while True:
                try:
                    with socket.create_connection((host, int(port)), timeout=0.5):
                        break
                except OSError:
                    if time.monotonic() >= deadline:
                        raise TimeoutError(
                            f"listener at {address} never became reachable"
                        )
                    time.sleep(0.05)

    # ------------------------------------------------------------------
    def close(self) -> None:
        """daemon.go:254-274 (Loader save happens in service.close)."""
        if self._closed:
            return
        self._closed = True
        if self._pool is not None:
            self._pool.close()
        if self.service is not None:
            self.service.close()
        if self.grpc is not None:
            self.grpc.close()
        if self.gateway is not None:
            self.gateway.close()


def spawn_daemon(conf: DaemonConfig, clock: Optional[Clock] = None) -> Daemon:
    """daemon.go:59-70."""
    return Daemon(conf, clock=clock).start()
