"""Local cluster binary (reference cmd/gubernator-cluster/main.go:30-56):
start an in-process loopback cluster for client-library testing; prints
"Ready" once all daemons accept connections."""

from __future__ import annotations

import argparse
import signal
import sys
import threading


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description="gubernator-tpu local cluster")
    parser.add_argument("--nodes", type=int, default=6)
    args = parser.parse_args(argv)

    from . import place_compile_cache

    place_compile_cache()

    from ..cluster import Cluster

    cl = Cluster().start(args.nodes)
    for p in cl.peers:
        print(f"peer: http://{p.http_address} grpc://{p.grpc_address}")
    print("Ready")
    sys.stdout.flush()

    stop = threading.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *_: stop.set())
    stop.wait()
    cl.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
