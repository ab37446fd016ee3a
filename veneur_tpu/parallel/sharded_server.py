"""The sharded serving plane: topology + accounting for the partitioned
column store.

One instance per server owns the device mesh the sharded tables
(core/sharded_tables.py) run on and the digest-home routing function
every family shares: a metric key's 64-bit fnv1a digest picks its home
shard once, at mint time, and every sample / import merge for that key
lands on that shard's slice of the partitioned state. The flush-time
merge is then a collective *selection* (parallel/collectives.py), which
is what keeps the llhist/HLL registers bit-identical to a single-device
table — the PR-5 exactness pin generalized to the mesh.

The plane is also the mesh's self-telemetry root: `mesh.*` rows
describe the topology (`mesh.merge_rounds`: one per family per flush
that had a generation to merge), `shard.*` rows the per-shard routing
volume, so an operator can see a skewed key space (one hot shard) or a
dead chip (a shard's routed-sample counter flatlining) straight off
/metrics; `ingest.shard.route_seconds_total{family}` is the host wall of
routing batches to shards (mask, tile, `device_put`), which one device
never pays.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional

import numpy as np

from veneur_tpu.parallel import collectives

ROUTING_DIGEST = "digest"
ROUTING_ROUNDROBIN = "roundrobin"


def local_shard_devices(n: int) -> List:
    """The first n local devices of the default platform. Asking for
    more shards than there are devices is an error: a mesh silently
    moved to other devices, or shrunk, would serve from a topology the
    operator did not configure."""
    import jax

    devices = jax.local_devices()
    if len(devices) < n:
        raise ValueError(
            f"{n} shards requested but only {len(devices)} local "
            f"{devices[0].platform} device(s) exist")
    return list(devices[:n])


class ShardedServingPlane:
    """Mesh topology + per-shard routing accounting, shared by every
    sharded family table of one column store."""

    def __init__(self, devices: List, routing: str = ROUTING_DIGEST):
        if routing not in (ROUTING_DIGEST, ROUTING_ROUNDROBIN):
            raise ValueError(f"unknown shard routing {routing!r}")
        self.devices = list(devices)
        self.n = len(self.devices)
        self.routing = routing
        self.mesh = collectives.local_mesh(self.devices)
        # per-shard routed-sample counters, keyed by family. The
        # flush thread's readout folds its last pending batch's counts
        # outside the table's apply lock while the ingest thread
        # applies, so the numpy read-modify-write adds need their own
        # leaf lock (scrapes stay lock-free point reads — one row stale
        # at worst)
        self._samples: Dict[str, np.ndarray] = {}
        # wall seconds spent routing batches to shards (mask, tile,
        # device_put), per family: host work one device never pays
        self._route_s: Dict[str, float] = {}
        self._acc_lock = threading.Lock()
        self.batches_dispatched = 0
        self.merge_rounds = 0

    # -- routing ---------------------------------------------------------

    def home(self, digest64: int) -> int:
        """One key's home shard: contiguous range partition of the
        64-bit digest space (top bits pick the shard, matching
        collectives.home_shards and the proxy ring's group split), so
        an N->M reshard migrates only the cells whose range boundary
        moved."""
        return ((int(digest64) & 0xFFFFFFFFFFFFFFFF) * self.n) >> 64

    def homes(self, digest64_arr) -> np.ndarray:
        return collectives.home_shards(digest64_arr, self.n)

    # -- accounting ------------------------------------------------------

    def note_routed(self, family: str, per_shard_counts,
                    route_s: float) -> None:
        """Fold one dispatch's per-shard sample counts (len n array)
        and the wall its routing took. Thread-safe: called from ingest
        (under table locks) AND from the background flush readout
        (lock-free by design)."""
        with self._acc_lock:
            acc = self._samples.get(family)
            if acc is None:
                acc = self._samples[family] = np.zeros(self.n, np.int64)
            acc += np.asarray(per_shard_counts, np.int64)
            self._route_s[family] = self._route_s.get(family, 0.0) + route_s
            self.batches_dispatched += 1

    def note_merge_round(self) -> None:
        with self._acc_lock:
            self.merge_rounds += 1

    # -- surfaces --------------------------------------------------------

    def describe(self) -> dict:
        """Topology summary for the startup flight-recorder event and
        /debug surfaces."""
        return {
            "shards": self.n,
            "routing": self.routing,
            "devices": [f"{d.platform}:{d.id}" for d in self.devices],
        }

    def telemetry_rows(self) -> List[tuple]:
        rows: List[tuple] = [
            ("mesh.shards", "gauge", float(self.n), ()),
            ("mesh.merge_rounds", "counter", float(self.merge_rounds), ()),
            ("mesh.batches_dispatched", "counter",
             float(self.batches_dispatched), ()),
        ]
        for family, acc in list(self._samples.items()):
            for shard, count in enumerate(acc.tolist()):
                rows.append(("shard.samples_routed", "counter",
                             float(count),
                             [f"family:{family}", f"shard:{shard}"]))
        for family, seconds in list(self._route_s.items()):
            rows.append(("ingest.shard.route_seconds_total", "counter",
                         seconds, [f"family:{family}"]))
        return rows


def build_plane(shards: int, routing: str = ROUTING_DIGEST
                ) -> Optional[ShardedServingPlane]:
    """Plane for `shards` local devices; None for a single-device
    store (shards <= 1)."""
    if not shards or shards <= 1:
        return None
    return ShardedServingPlane(local_shard_devices(shards),
                               routing=routing)
