"""gRPC data plane — serves `pb.gubernator.V1` and `pb.gubernator.PeersV1`.

Parity with the reference's gRPC server registration
(gubernator.go:72-76, daemon.go:86-136): both services share one
grpc.Server, receive size is capped at 1 MiB (daemon.go:88), and TLS /
mTLS credentials wrap the port (daemon.go:102-106).  Service stubs are
wired with `grpc.method_handlers_generic_handler` over the protoc
message classes (no grpc_python_plugin in this image), so the wire
format and fully-qualified method names match the reference exactly —
a stock Gubernator client can dial this server.
"""

from __future__ import annotations

import logging
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import grpc

from . import tracing
from .saturation import phase
from . import wire
from .config import INGRESS_COLUMNS_MAX_LANES, PEER_COLUMNS_MAX_LANES
from .proto import PEERS_V1_SERVICE, V1_SERVICE
from .proto import gubernator_pb2 as pb
from .proto import peers_columns_pb2 as pc_pb
from .proto import peers_pb2 as peers_pb
from .service import ApiError, V1Service

log = logging.getLogger("gubernator.grpc")

MAX_RECV_BYTES = 1024 * 1024  # daemon.go:88


class MetricsInterceptor(grpc.ServerInterceptor):
    """Per-RPC stats at the TRANSPORT layer (reference GRPCStatsHandler,
    grpc_stats.go:95-118): every method served by this grpc.Server —
    including ones added later — is counted and timed under
    gubernator_grpc_request_counts / gubernator_grpc_request_duration,
    with no per-handler hand-instrumentation.  An abort() or raise
    counts as status="1"."""

    def __init__(self, metrics):
        self.metrics = metrics

    def intercept_service(self, continuation, handler_call_details):
        handler = continuation(handler_call_details)
        if handler is None or self.metrics is None or handler.unary_unary is None:
            return handler  # only unary-unary methods exist here
        inner = handler.unary_unary
        method = handler_call_details.method
        # W3C trace-context ingress (tracing.py): extract `traceparent`
        # from the invocation metadata, run the handler under the span,
        # and emit the context back as trailing metadata so callers can
        # join logs/traces on one id.  Zero-cost when tracing is off —
        # ingress_span returns the shared no-op.
        traceparent = None
        for k, v in handler_call_details.invocation_metadata or ():
            if k == "traceparent":
                traceparent = v
                break

        def wrapped(request, context):
            # Span OUTSIDE the metrics timer: observe_rpc's exit hook
            # attaches a trace exemplar from the still-active context.
            with tracing.ingress_span("grpc", method, traceparent) as sp:
                with self.metrics.observe_rpc(method):
                    resp = inner(request, context)
                    tp = sp.traceparent()
                    if tp is not None:
                        context.set_trailing_metadata((("traceparent", tp),))
                    return resp

        return grpc.unary_unary_rpc_method_handler(
            wrapped,
            request_deserializer=handler.request_deserializer,
            response_serializer=handler.response_serializer,
        )

_STATUS_CODES = {
    "InvalidArgument": grpc.StatusCode.INVALID_ARGUMENT,
    "OutOfRange": grpc.StatusCode.OUT_OF_RANGE,
    "Internal": grpc.StatusCode.INTERNAL,
    # The reshard epoch fence (service.transfer_ownership): a transfer
    # stamped with a dead ring's fingerprint must not commit, and the
    # sender must see a distinct, non-retryable answer.
    "FailedPrecondition": grpc.StatusCode.FAILED_PRECONDITION,
}


class GrpcServer:
    """One gRPC listener serving both services."""

    def __init__(
        self,
        service: V1Service,
        listen_address: str = "127.0.0.1:0",
        tls_conf=None,  # Optional[tls.TLSConfig] (file paths already resolved)
        # Handlers BLOCK on device rounds, so this pool caps in-flight
        # RPCs — and therefore how many concurrent callers one
        # coalescing window can merge (the same convoy as a bounded
        # HTTP worker pool).  128 covers the reference's
        # 100-way benchmark fan-in; idle-blocked threads are cheap.
        max_workers: int = 128,
        max_conn_age_s: int = 0,
    ):
        self.service = service
        options = [
            ("grpc.max_receive_message_length", MAX_RECV_BYTES),
            ("grpc.so_reuseport", 0),
        ]
        if max_conn_age_s > 0:
            # GUBER_GRPC_MAX_CONN_AGE_SEC (daemon.go:91-96): rotate
            # long-lived client connections so load rebalances across a
            # changing cluster; same 30s grace the reference sets.
            options.append(("grpc.max_connection_age_ms", max_conn_age_s * 1000))
            options.append(("grpc.max_connection_age_grace_ms", 30 * 1000))
        self._server = grpc.server(
            ThreadPoolExecutor(max_workers=max_workers, thread_name_prefix="grpc"),
            options=options,
            interceptors=(MetricsInterceptor(service.metrics),),
        )
        self._server.add_generic_rpc_handlers(
            (_v1_handler(service), _peers_v1_handler(service))
        )
        host, _, port = listen_address.partition(":")
        target = f"{host or '127.0.0.1'}:{port or 0}"
        if tls_conf is not None and tls_conf.enabled:
            creds = server_credentials(tls_conf)
            bound = self._server.add_secure_port(target, creds)
        else:
            bound = self._server.add_insecure_port(target)
        if bound == 0:
            raise OSError(f"gRPC server failed to bind {target}")
        self.address = f"{host or '127.0.0.1'}:{bound}"

    def start(self) -> "GrpcServer":
        self._server.start()
        return self

    def close(self, grace_s: float = 0.5) -> None:
        self._server.stop(grace=grace_s).wait(timeout=grace_s + 1.0)


def server_credentials(tls_conf) -> grpc.ServerCredentials:
    """Build grpc server creds from a resolved TLSConfig (tls.go:118-263:
    cert chain + optional client-auth CA; require-and-verify maps to
    require_client_auth)."""
    with open(tls_conf.cert_file, "rb") as f:
        cert = f.read()
    with open(tls_conf.key_file, "rb") as f:
        key = f.read()
    root = None
    require = False
    if tls_conf.client_auth:
        ca_file = tls_conf.client_auth_ca_file or tls_conf.ca_file
        with open(ca_file, "rb") as f:
            root = f.read()
        require = tls_conf.client_auth == "require-and-verify"
    return grpc.ssl_server_credentials(
        [(key, cert)], root_certificates=root, require_client_auth=require
    )


def channel_credentials(tls_conf) -> grpc.ChannelCredentials:
    """Client-side creds: trust the configured CA, present this node's
    client cert under mTLS (tls.go:188-207 equivalent)."""
    root = None
    if tls_conf.ca_file:
        with open(tls_conf.ca_file, "rb") as f:
            root = f.read()
    key = cert = None
    cert_file = tls_conf.client_auth_cert_file or (
        tls_conf.cert_file if tls_conf.client_auth else ""
    )
    key_file = tls_conf.client_auth_key_file or (
        tls_conf.key_file if tls_conf.client_auth else ""
    )
    if cert_file:
        with open(cert_file, "rb") as f:
            cert = f.read()
        with open(key_file, "rb") as f:
            key = f.read()
    return grpc.ssl_channel_credentials(
        root_certificates=root, private_key=key, certificate_chain=cert
    )


def _abort_api_error(context: grpc.ServicerContext, e: ApiError):
    context.abort(_STATUS_CODES.get(e.code, grpc.StatusCode.UNKNOWN), e.message)


def _v1_handler(service: V1Service) -> grpc.GenericRpcHandler:
    def get_rate_limits(request: pb.GetRateLimitsReq, context) -> pb.GetRateLimitsResp:
        try:
            result = service.get_rate_limits_columns(wire.columns_from_pb(request))
            return wire.columns_to_pb(result)
        except ApiError as e:
            _abort_api_error(context, e)

    def get_rate_limits_columns(
        request: pc_pb.PeerColumnsReq, context
    ) -> pc_pb.IngressColumnsResp:
        """The public columnar ingress (the front door, wire.py "public
        columnar ingress"): proto columns decode straight into
        IngressColumns and the result arrays — owner annotation
        included — serialize straight back, no per-lane dataclasses
        either way."""
        try:
            # Untrusted-client validation, the HTTP frame edge's twin
            # (wire._decode_req_frame validate=True) — the two
            # transports must not diverge.  Ragged columns would crash
            # the decode (or silently truncate); an out-of-range
            # algorithm must not reach the kernel as a garbage branch
            # selector.
            n = len(request.names)
            if any(
                len(col) != n
                for col in (
                    request.unique_keys, request.algorithm,
                    request.behavior, request.hits, request.limit,
                    request.duration,
                )
            ):
                raise ApiError(
                    "InvalidArgument", "column length mismatch"
                )
            with phase("ingress.parse"):
                cols = wire.ingress_from_peer_columns_pb(request)
            if len(cols) and bool(
                ((cols.algorithm < 0) | (cols.algorithm > 1)).any()
            ):
                raise ApiError(
                    "InvalidArgument", "algorithm out of range"
                )
            result = service.get_rate_limits_columns(
                cols, max_lanes=INGRESS_COLUMNS_MAX_LANES,
            )
            with phase("response.encode"):
                resp = wire.result_to_ingress_columns_pb(result)
            service.metrics.ingress_columns_batches.labels(
                encoding="proto"
            ).inc()
            return resp
        except ApiError as e:
            _abort_api_error(context, e)

    def health_check(request: pb.HealthCheckReq, context) -> pb.HealthCheckResp:
        return wire.health_to_pb(service.health_check())

    methods = {
        "GetRateLimits": grpc.unary_unary_rpc_method_handler(
            get_rate_limits,
            request_deserializer=pb.GetRateLimitsReq.FromString,
            response_serializer=pb.GetRateLimitsResp.SerializeToString,
        ),
        "HealthCheck": grpc.unary_unary_rpc_method_handler(
            health_check,
            request_deserializer=pb.HealthCheckReq.FromString,
            response_serializer=pb.HealthCheckResp.SerializeToString,
        ),
    }
    if service.serves_ingress_columns:
        # The shared advertisement rule (V1Service.serves_ingress_
        # columns): GUBER_INGRESS_COLUMNS=0 — or a store without
        # columnar support — withholds the method entirely, so clients
        # see UNIMPLEMENTED, exactly what a pre-columns daemon answers
        # (the mixed-version interop mode).
        methods["GetRateLimitsColumns"] = grpc.unary_unary_rpc_method_handler(
            get_rate_limits_columns,
            request_deserializer=pc_pb.PeerColumnsReq.FromString,
            response_serializer=pc_pb.IngressColumnsResp.SerializeToString,
        )
    return grpc.method_handlers_generic_handler(V1_SERVICE, methods)


def _peers_v1_handler(service: V1Service) -> grpc.GenericRpcHandler:
    def get_peer_rate_limits(
        request: peers_pb.GetPeerRateLimitsReq, context
    ) -> peers_pb.GetPeerRateLimitsResp:
        try:
            result = service.get_peer_rate_limits_columns(
                wire.columns_from_pb(request)
            )
            return wire.columns_to_peer_pb(result)
        except ApiError as e:
            _abort_api_error(context, e)

    def get_peer_rate_limits_columns(
        request: pc_pb.PeerColumnsReq, context
    ) -> pc_pb.PeerColumnsResp:
        """The columnar peer hop (peers_columns.proto): proto columns
        decode straight into IngressColumns and the result arrays
        serialize straight back — no per-lane dataclasses either way."""
        try:
            with phase("ingress.parse"):
                cols = wire.ingress_from_peer_columns_pb(request)
            result = service.get_peer_rate_limits_columns(
                cols, max_lanes=PEER_COLUMNS_MAX_LANES,
            )
            with phase("response.encode"):
                return wire.result_to_peer_columns_pb(result)
        except ApiError as e:
            _abort_api_error(context, e)

    def update_peer_globals(
        request: peers_pb.UpdatePeerGlobalsReq, context
    ) -> peers_pb.UpdatePeerGlobalsResp:
        service.update_peer_globals(wire.update_globals_req_from_pb(request))
        return peers_pb.UpdatePeerGlobalsResp()

    def update_peer_globals_columns(
        request: pc_pb.GlobalsColumnsReq, context
    ) -> peers_pb.UpdatePeerGlobalsResp:
        """Columnar GLOBAL broadcast receive (peers_columns.proto
        GlobalsColumnsReq): the whole batch decodes into arrays and
        commits as ONE replica scatter (store.set_replica_batch)."""
        try:
            service.update_peer_globals_columns(
                wire.globals_cols_from_pb(request)
            )
            return peers_pb.UpdatePeerGlobalsResp()
        except ApiError as e:
            _abort_api_error(context, e)

    def update_region_columns(
        request: pc_pb.RegionColumnsReq, context
    ) -> pc_pb.RegionColumnsResp:
        """Cross-region federation receive (federation.py): one
        columnar hit batch from a remote region's flush, applied
        through the same columnar path a classic per-item send lands
        in (service.update_region_columns)."""
        try:
            applied = service.update_region_columns(
                wire.region_cols_from_pb(request)
            )
            return pc_pb.RegionColumnsResp(applied=applied)
        except ApiError as e:
            _abort_api_error(context, e)

    def transfer_ownership(
        request: pc_pb.TransferColumnsReq, context
    ) -> pc_pb.TransferResp:
        """Ownership-transfer receive (elastic membership, reshard.py):
        the whole batch merge-commits through ONE batched device
        gather+scatter (store.commit_transfer); a dead-epoch batch is
        fenced with FAILED_PRECONDITION."""
        try:
            committed, rejected = service.transfer_ownership(
                wire.transfer_cols_from_pb(request)
            )
            return pc_pb.TransferResp(committed=committed, rejected=rejected)
        except ApiError as e:
            _abort_api_error(context, e)

    methods = {
        "GetPeerRateLimits": grpc.unary_unary_rpc_method_handler(
            get_peer_rate_limits,
            request_deserializer=peers_pb.GetPeerRateLimitsReq.FromString,
            response_serializer=peers_pb.GetPeerRateLimitsResp.SerializeToString,
        ),
        "UpdatePeerGlobals": grpc.unary_unary_rpc_method_handler(
            update_peer_globals,
            request_deserializer=peers_pb.UpdatePeerGlobalsReq.FromString,
            response_serializer=peers_pb.UpdatePeerGlobalsResp.SerializeToString,
        ),
    }
    if service.serves_peer_columns:
        # The shared advertisement rule (V1Service.serves_peer_columns):
        # GUBER_PEER_COLUMNS=0 — or a store without columnar support —
        # withholds the method entirely, so callers see UNIMPLEMENTED,
        # exactly what a pre-columns daemon answers (the mixed-version
        # interop mode).
        methods["GetPeerRateLimitsColumns"] = grpc.unary_unary_rpc_method_handler(
            get_peer_rate_limits_columns,
            request_deserializer=pc_pb.PeerColumnsReq.FromString,
            response_serializer=pc_pb.PeerColumnsResp.SerializeToString,
        )
    if service.serves_global_columns:
        # Same advertisement rule as the forward hop, on its own knob
        # (V1Service.serves_global_columns): GUBER_GLOBAL_COLUMNS=0
        # withholds the method so senders see UNIMPLEMENTED — exactly
        # what a pre-columns daemon answers — and fall back to the
        # classic per-item UpdatePeerGlobals.
        methods["UpdatePeerGlobalsColumns"] = grpc.unary_unary_rpc_method_handler(
            update_peer_globals_columns,
            request_deserializer=pc_pb.GlobalsColumnsReq.FromString,
            response_serializer=peers_pb.UpdatePeerGlobalsResp.SerializeToString,
        )
    if service.serves_region_columns:
        # Same advertisement rule on the federation knob
        # (V1Service.serves_region_columns): GUBER_REGION_COLUMNS=0
        # withholds the method so senders see UNIMPLEMENTED — exactly
        # what a pre-federation daemon answers — and fall back sticky
        # to the classic per-item GetPeerRateLimits encoding.
        methods["UpdateRegionColumns"] = grpc.unary_unary_rpc_method_handler(
            update_region_columns,
            request_deserializer=pc_pb.RegionColumnsReq.FromString,
            response_serializer=pc_pb.RegionColumnsResp.SerializeToString,
        )
    if service.serves_reshard:
        # Same advertisement rule on the reshard knob
        # (V1Service.serves_reshard): GUBER_RESHARD=0 withholds the
        # method so senders see UNIMPLEMENTED — exactly what a
        # pre-reshard daemon answers — and degrade sticky to the
        # classic (reset-on-move) behavior for this peer.
        methods["TransferOwnership"] = grpc.unary_unary_rpc_method_handler(
            transfer_ownership,
            request_deserializer=pc_pb.TransferColumnsReq.FromString,
            response_serializer=pc_pb.TransferResp.SerializeToString,
        )
    return grpc.method_handlers_generic_handler(PEERS_V1_SERVICE, methods)
