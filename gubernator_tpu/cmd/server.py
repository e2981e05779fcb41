"""Server binary (reference cmd/gubernator/main.go): flags -> daemon."""

from __future__ import annotations

import argparse
import signal
import sys
import threading


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description="gubernator-tpu rate-limit daemon")
    parser.add_argument("-config", dest="config", default="", help="env config file")
    parser.add_argument("-debug", dest="debug", action="store_true", help="debug logging")
    parser.add_argument(
        "-version", "--version", dest="version", action="store_true",
        help="print version and exit",
    )
    args = parser.parse_args(argv)

    if args.version:
        from .. import __version__

        print(f"gubernator-tpu {__version__}")
        return 0

    from . import place_compile_cache

    place_compile_cache()

    from ..config import setup_daemon_config
    from ..daemon import spawn_daemon
    from ..utils.logging import setup_logging

    from .. import telemetry

    conf = setup_daemon_config(config_file=args.config)
    if args.debug:
        conf.debug = True
    setup_logging(debug=conf.debug)
    # Set-up by part (/debug/device `startup`): the interpreter, the
    # imports (jax among them) and the configuration, from the process's
    # own start; `Daemon._start` times the parts that follow.
    telemetry.note_startup("imports", telemetry.process_age_s())
    daemon = spawn_daemon(conf)
    addr = daemon.gateway.address
    telemetry.note_listening()
    print(f"gubernator-tpu listening on http://{addr} (advertise {daemon.peer_info.grpc_address})")
    sys.stdout.flush()

    stop = threading.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *_: stop.set())
    stop.wait()
    daemon.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
