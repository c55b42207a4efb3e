"""Resource accounting per node (paper P3: resource-awareness).

The paper's manager watches CPU/RAM per Raspberry Pi; here the scarce
resources per node (mesh slice) are HBM bytes and sustained FLOP/s.  The
monitor tracks commitments (deployed executor footprints + in-flight work)
and answers admission queries.  A node backed by accelerators reads its
capacity from the devices (``NodeCapacity.of_devices``); logical nodes in
tests and CPU demos declare an explicit one.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Dict, Iterable

# Published peak dense bf16 FLOP/s per chip, keyed by ``Device.device_kind``.
# Source: Google Cloud TPU documentation, "TPU v5e" (197 TFLOP/s bf16).
PEAK_FLOPS_BY_KIND = {
    "TPU v5 lite": 197e12,
}


@dataclasses.dataclass
class NodeCapacity:
    hbm_bytes: int
    chips: int = 1
    flops_per_s: float = 0.0

    @classmethod
    def of_devices(cls, devices: Iterable) -> "NodeCapacity":
        """Capacity of the accelerators a node owns, read from the devices:
        HBM is the runtime's ``memory_stats()["bytes_limit"]`` and FLOP/s
        the published peak for the device kind.  A device kind without a
        published peak, or one that reports no memory limit (the CPU
        backend), raises — such nodes pass an explicit capacity."""
        devices = list(devices)
        hbm, flops = 0, 0.0
        for d in devices:
            peak = PEAK_FLOPS_BY_KIND.get(d.device_kind)
            stats = d.memory_stats() or {}
            if peak is None or "bytes_limit" not in stats:
                raise ValueError(
                    f"no known capacity for device kind {d.device_kind!r} "
                    f"(peaks known for {sorted(PEAK_FLOPS_BY_KIND)}); pass "
                    f"an explicit NodeCapacity")
            hbm += int(stats["bytes_limit"])
            flops += peak
        return cls(hbm_bytes=hbm, chips=len(devices), flops_per_s=flops)


@dataclasses.dataclass
class Commitment:
    hbm_bytes: int
    flops_inflight: float = 0.0


class ResourceMonitor:
    def __init__(self):
        self._lock = threading.Lock()
        self.capacity: Dict[str, NodeCapacity] = {}
        self.committed: Dict[str, Dict[str, Commitment]] = {}

    def register_node(self, node_id: str, capacity: NodeCapacity):
        with self._lock:
            self.capacity[node_id] = capacity
            self.committed.setdefault(node_id, {})

    def unregister_node(self, node_id: str):
        with self._lock:
            self.capacity.pop(node_id, None)
            self.committed.pop(node_id, None)

    # ------------------------------------------------------------------
    def hbm_free(self, node_id: str) -> int:
        with self._lock:
            cap = self.capacity[node_id].hbm_bytes
            used = sum(c.hbm_bytes for c in self.committed[node_id].values())
            return cap - used

    def hbm_utilization(self, node_id: str) -> float:
        # ONE snapshot under the lock: reading capacity and the committed
        # sum separately races unregister_node (KeyError mid-failover)
        with self._lock:
            node_cap = self.capacity.get(node_id)
            if node_cap is None:
                return 1.0
            cap = node_cap.hbm_bytes
            used = sum(c.hbm_bytes for c in self.committed[node_id].values())
        return used / cap if cap else 1.0

    def fits(self, node_id: str, hbm_bytes: int, spec=None) -> bool:
        """``spec`` is accepted (and ignored) so the monitor stays
        call-compatible with the quota-aware ``AdmissionController.fits``
        that placement policies normally score against."""
        with self._lock:
            node_cap = self.capacity.get(node_id)
            if node_cap is None:
                return False
            used = sum(c.hbm_bytes for c in self.committed[node_id].values())
            return node_cap.hbm_bytes - used >= hbm_bytes

    def commit(self, node_id: str, key: str, hbm_bytes: int) -> bool:
        """Atomic admission: reserve or refuse (paper: avoid overload)."""
        with self._lock:
            cap = self.capacity.get(node_id)
            if cap is None:
                return False
            used = sum(c.hbm_bytes for c in self.committed[node_id].values())
            if used + hbm_bytes > cap.hbm_bytes:
                return False
            self.committed[node_id][key] = Commitment(hbm_bytes=hbm_bytes)
            return True

    def release(self, node_id: str, key: str):
        with self._lock:
            if node_id in self.committed:
                self.committed[node_id].pop(key, None)

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {
                n: {
                    "hbm_total": float(self.capacity[n].hbm_bytes),
                    "hbm_used": float(sum(
                        c.hbm_bytes for c in self.committed[n].values())),
                    "instances": float(len(self.committed[n])),
                }
                for n in self.capacity
            }
