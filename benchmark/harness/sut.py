"""The system under test, started the way `python -m veneur_tpu.cmd.veneur
-f` starts it, and what the benchmark reads from it: its HTTP API, JAX's
compile events and the device stamp. Only the parent process imports
this (it is the one module here that touches JAX and the program).
"""

from __future__ import annotations

import json
import logging
import os
import socket

# what JAX must report; the CPU rehearsal (benchmark/tests) steers this
# one constant to "cpu"
REQUIRED_PLATFORM = "tpu"


class NoDevice(SystemExit):
    pass


def require_devices(count: int):
    """The devices JAX reports, or exit: a run means nothing on a
    platform other than the one asked for."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != REQUIRED_PLATFORM:
        raise NoDevice(f"benchmark: JAX found platform {platform!r} "
                       f"({len(devices)} device(s)), not "
                       f"{REQUIRED_PLATFORM!r}")
    if len(devices) < count:
        raise NoDevice(f"benchmark: {count} {platform} device(s) needed, "
                       f"JAX found {len(devices)}")
    return devices[:count]


def device_stamp(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak on the fullest chip; 0 where the backend reports none."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


class _CompileNames(logging.Handler):
    """Keeps the name of every program JAX says it compiles; records at
    WARNING and above go on to the root logger as they would have."""

    def __init__(self, names: list) -> None:
        super().__init__(logging.DEBUG)
        self.names = names

    def emit(self, record) -> None:
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(msg[len("Compiling "):].split(" with ")[0])
        elif record.levelno >= logging.WARNING:
            logging.getLogger().handle(record)


class CompileMeter:
    """Backend compile seconds and persistent-cache hits/misses, from
    JAX's own monitoring events, and the names of the programs compiled
    or loaded from the cache (both cost the thread that asked)."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.seconds, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0
        self.names: list = []
        self._logger = logging.getLogger("jax._src.interpreters.pxla")
        self._was = (self._logger.level, self._logger.propagate)
        self._handler = _CompileNames(self.names)
        self._logger.addHandler(self._handler)
        self._logger.setLevel(logging.DEBUG)
        self._logger.propagate = False
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def close(self) -> None:
        self._logger.removeHandler(self._handler)
        self._logger.setLevel(self._was[0])
        self._logger.propagate = self._was[1]

    def _duration(self, event, duration, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": round(self.seconds, 3), "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}


def write_config(repo_root: str, out_dir: str, name: str, config: dict,
                 intake_port: int) -> str:
    """examples/example.yaml with the deployment's overrides, loopback
    port-0 listeners, and one Datadog sink posting to the benchmark's
    intake, every other sink key at its default."""
    import yaml

    with open(os.path.join(repo_root, "examples", "example.yaml")) as f:
        raw = yaml.safe_load(f)
    raw.update(
        interval=f"{config['interval_s']}s",
        hostname="benchmark",
        statsd_listen_addresses=["udp://127.0.0.1:0"],
        ssf_listen_addresses=[],
        grpc_address="",
        http_address="127.0.0.1:0",
        http_quit=False,
        percentiles=list(config["percentiles"]),
        metric_sinks=[{"kind": "datadog", "name": "datadog", "config": {
            "datadog_api_key": "benchmark",
            "datadog_api_hostname": f"http://127.0.0.1:{intake_port}"}}],
    )
    overrides = dict(config.get("overrides", {}))
    raw["tpu"].update(overrides.pop("tpu", {}))
    raw.update(overrides)
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{name}.yaml")
    with open(path, "w") as f:
        yaml.safe_dump(raw, f, sort_keys=False)
    return path


def start_server(config_path: str):
    from veneur_tpu import native
    from veneur_tpu.config import read_config
    from veneur_tpu.core.server import Server

    if not native.available():
        raise SystemExit("benchmark: native parser unavailable: "
                         f"{native.unavailable_reason()}")
    server = Server(read_config(config_path))
    server.start()
    if getattr(server._listeners[0], "pump", None) is None:
        raise SystemExit(
            "benchmark: the UDP listener did not start the native pump rung")
    return server


def rcvbuf_bytes(server) -> list:
    """The effective SO_RCVBUF of each statsd reader socket (the
    configured size is clamped by the machine's rmem_max, and Linux
    reports twice what it granted)."""
    return [s.getsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF)
            for s in server._listeners[0]._socks]


class Api:
    """The server's HTTP API, as a client sees it."""

    def __init__(self, server):
        self.base = "http://%s:%d" % tuple(server.http_api.address[:2])

    def get(self, path: str) -> bytes:
        from veneur_tpu.util import http

        status, body = http.get(self.base + path, timeout=60.0)
        if status != 200:
            raise SystemExit(f"benchmark: GET {path} answered {status}")
        return body

    def json(self, path: str):
        return json.loads(self.get(path))

    def prometheus(self) -> dict:
        """{row name without labels: summed value} of GET /metrics."""
        rows: dict = {}
        for line in self.get("/metrics").decode().splitlines():
            if not line or line.startswith("#"):
                continue
            head, _, value = line.rpartition(" ")
            name = head.split("{", 1)[0]
            try:
                rows[name] = rows.get(name, 0.0) + float(value)
            except ValueError:
                pass
        return rows
