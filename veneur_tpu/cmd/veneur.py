"""The veneur-tpu server CLI.

Parity with reference cmd/veneur/main.go:44-200: load YAML config with
VENEUR_* env overlay, optional -validate-config[-strict] modes, wire
sinks/sources, start the server, and block until SIGINT/SIGTERM
(flush-on-shutdown honored by Server.shutdown).

Run: python -m veneur_tpu.cmd.veneur -f config.yaml
"""

from __future__ import annotations

import argparse
import logging
import signal
import sys
import threading

import veneur_tpu
from veneur_tpu.config import read_config


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="veneur")
    ap.add_argument("-f", dest="config", required=False,
                    help="YAML config file")
    ap.add_argument("-validate-config", action="store_true",
                    dest="validate_config",
                    help="parse the config and exit")
    ap.add_argument("-validate-config-strict", action="store_true",
                    dest="validate_strict",
                    help="parse the config rejecting unknown keys, and exit")
    ap.add_argument("-version", action="store_true", dest="version")
    ap.add_argument("-debug", action="store_true")
    args = ap.parse_args(argv)

    if args.version:
        print(veneur_tpu.__version__)
        return 0

    logging.basicConfig(
        level=logging.DEBUG if args.debug else logging.INFO,
        format="%(asctime)s %(levelname)s %(name)s %(message)s")
    log = logging.getLogger("veneur")

    try:
        cfg = read_config(args.config, strict=args.validate_strict)
    except Exception as e:
        log.error("could not read config: %s", e)
        return 1
    if args.validate_config or args.validate_strict:
        print("config OK")
        return 0
    if args.debug:
        cfg.debug = True

    # crash reporting: ERROR+ records and thread panics route to the
    # registered reporters (reference sentry.go + the logrus hook,
    # cmd/veneur/main.go:63-79); sentry-sdk is optional and gated
    from veneur_tpu.util import crash
    logging.getLogger().addHandler(crash.ReportingHandler())
    if cfg.sentry_dsn:
        try:
            import sentry_sdk
            sentry_sdk.init(dsn=cfg.sentry_dsn.reveal())
            crash.register_reporter(
                lambda exc, tb: sentry_sdk.capture_exception(exc))
        except ImportError:
            log.warning("sentry_dsn set but sentry-sdk is unavailable; "
                        "crashes log locally only")

    from veneur_tpu.core.server import Server
    server = Server(cfg)
    server.start()
    log.info("veneur-tpu %s started (local=%s, statsd=%s, ssf=%s, http=%s, "
             "device=%s)",
             veneur_tpu.__version__, server.is_local,
             cfg.statsd_listen_addresses, cfg.ssf_listen_addresses,
             cfg.http_address, server.device_info)

    stop = threading.Event()

    def handle_signal(signum, frame):
        log.info("received signal %d, shutting down", signum)
        stop.set()

    signal.signal(signal.SIGINT, handle_signal)
    signal.signal(signal.SIGTERM, handle_signal)

    # SIGHUP: hot-reload the `alerts:` block from the config file —
    # rule table swaps in place, in-flight alert state survives for
    # rule ids present in both tables. A bad table keeps the old one.
    def handle_hup(signum, frame):
        def _reload():
            try:
                server.reload_alerts(args.config)
            except Exception:
                log.exception("SIGHUP alert reload failed; "
                              "keeping the previous rule table")
        threading.Thread(target=_reload, name="alert-reload",
                         daemon=True).start()

    if hasattr(signal, "SIGHUP"):
        signal.signal(signal.SIGHUP, handle_hup)
    # SIGUSR2: zero-gap graceful restart via SO_REUSEPORT handoff (the
    # einhorn equivalent, reference server.go:1404, README.md:170-178)
    from veneur_tpu.core import restart
    restart.install(server.shutdown, cfg.http_address)
    # exit on signal OR on internally-triggered shutdown (/quitquitquit)
    while not stop.is_set() and not server.shutdown_complete.is_set():
        stop.wait(0.2)
    if not server.shutdown_complete.is_set():
        server.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
