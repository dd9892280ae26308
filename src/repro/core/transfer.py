"""KV-cache transfer paths between prefill and decode accelerators.

The paper's benchmarked variable (section IV-F). TPU adaptation per
DESIGN.md section 2:

  ici    GPU-P2P analogue: slice-to-slice ICI transfer (one hop, pushed
         directly into the decode accelerator's HBM)         -> dis-gpu
  host   CPU-DRAM staging: device ->PCIe-> host DRAM, then DRAM ->PCIe->
         device, with a lookup-table round trip (Redis)      -> dis-cpu
  disk   NVMe staging: host path + O_DIRECT-style full write+read
         (page cache bypassed, as the paper forces)          -> dis-disk

Every path is split into a STORE half (prefill side; its latency lands in
TTFT) and a FETCH half (decode side; it occupies the decode engine at
admission, so slower media degrade TPOT) — mirroring the LMCache connector
structure the paper instruments. For the ici path the store pushes straight
into decode HBM and the fetch is free.

``store()``/``fetch()`` also REALLY move the state pytree (integration
tests assert bit-exact round trips, including the disk serialization).
``device`` is the decode engine's device: the payload lands there (None:
the default device).
"""
from __future__ import annotations

import io
import os
import pickle
import tempfile
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

from .costs import HostSpec


@dataclass
class LegCost:
    latency_s: float
    energy_j: Dict[str, float] = field(default_factory=dict)
    busy: Dict[str, float] = field(default_factory=dict)


class TransferPath:
    name = "base"

    def __init__(self, host: Optional[HostSpec] = None):
        self.host = host or HostSpec()

    # timing/energy model ------------------------------------------------
    def store_cost(self, nbytes: int) -> LegCost:
        raise NotImplementedError

    def fetch_cost(self, nbytes: int) -> LegCost:
        raise NotImplementedError

    # real byte movement ----------------------------------------------------
    def store(self, state: Any, device=None) -> Any:
        """state pytree -> opaque handle held by the medium."""
        return state

    def fetch(self, handle: Any, device=None) -> Any:
        """handle -> state pytree on the decode side's ``device``."""
        return handle


class ICIPath(TransferPath):
    """Device-to-device over the inter-slice interconnect (dis-gpu analog)."""

    name = "ici"

    def __init__(self, host=None, ici_bw: float = 200e9,
                 launch_latency_s: float = 20e-6):
        super().__init__(host)
        self.ici_bw = ici_bw
        self.launch_latency_s = launch_latency_s

    def store_cost(self, nbytes: int) -> LegCost:
        t = self.launch_latency_s + nbytes / self.ici_bw
        return LegCost(latency_s=t,
                       energy_j={"ici": nbytes * self.host.ici_pj_per_byte
                                 * 1e-12},
                       busy={"ici": t})

    def fetch_cost(self, nbytes: int) -> LegCost:
        return LegCost(latency_s=0.0)   # already resident in decode HBM

    def store(self, state: Any, device=None) -> Any:
        import jax
        return jax.device_put(state, device)   # straight into decode HBM

    def fetch(self, handle: Any, device=None) -> Any:
        return handle


class HostPath(TransferPath):
    """Device -> host DRAM -> device staging (dis-cpu analog)."""

    name = "host"

    def __init__(self, host=None, lookup_latency_s: float = 200e-6):
        super().__init__(host)
        self.lookup_latency_s = lookup_latency_s   # Redis index round trip

    def _leg(self, nbytes: int) -> LegCost:
        h = self.host
        t = nbytes / h.pcie_bw + self.lookup_latency_s
        return LegCost(
            latency_s=t,
            energy_j={
                "pcie": nbytes * h.pcie_pj_per_byte * 1e-12,
                "dram": nbytes * h.dram_pj_per_byte * 1e-12,
                "cpu": (h.cpu_active_w - h.cpu_idle_w) * t,
            },
            busy={"cpu": t, "dram": t},
        )

    def store_cost(self, nbytes: int) -> LegCost:
        return self._leg(nbytes)

    def fetch_cost(self, nbytes: int) -> LegCost:
        return self._leg(nbytes)

    def store(self, state: Any, device=None) -> Any:
        import jax
        import numpy as np
        return jax.tree.map(lambda x: np.asarray(x), state)   # -> host DRAM

    def fetch(self, handle: Any, device=None) -> Any:
        import jax
        return jax.device_put(handle, device)


class DiskPath(TransferPath):
    """Host staging + NVMe write/read, page cache bypassed (dis-disk)."""

    name = "disk"

    def __init__(self, host=None, scratch_dir: Optional[str] = None,
                 lookup_latency_s: float = 200e-6):
        super().__init__(host)
        self.scratch_dir = scratch_dir
        self.lookup_latency_s = lookup_latency_s

    def store_cost(self, nbytes: int) -> LegCost:
        h = self.host
        t_disk = nbytes / h.disk_write_bw
        t = nbytes / h.pcie_bw + t_disk + self.lookup_latency_s
        return LegCost(
            latency_s=t,
            energy_j={
                "pcie": nbytes * h.pcie_pj_per_byte * 1e-12,
                "dram": nbytes * h.dram_pj_per_byte * 1e-12,
                "disk": nbytes * h.disk_nj_per_byte * 1e-9,
                "cpu": (h.cpu_active_w - h.cpu_idle_w) * t,
            },
            busy={"cpu": t, "dram": t, "disk": t_disk},
        )

    def fetch_cost(self, nbytes: int) -> LegCost:
        h = self.host
        t_disk = nbytes / h.disk_read_bw
        t = t_disk + nbytes / h.pcie_bw + self.lookup_latency_s
        return LegCost(
            latency_s=t,
            energy_j={
                "pcie": nbytes * h.pcie_pj_per_byte * 1e-12,
                "dram": nbytes * h.dram_pj_per_byte * 1e-12,
                "disk": nbytes * h.disk_nj_per_byte * 1e-9,
                "cpu": (h.cpu_active_w - h.cpu_idle_w) * t,
            },
            busy={"cpu": t, "dram": t, "disk": t_disk},
        )

    def store(self, state: Any, device=None) -> Any:
        import jax
        import numpy as np
        buf = io.BytesIO()
        pickle.dump(jax.tree.map(lambda x: np.asarray(x), state), buf)
        data = buf.getvalue()
        fd, path = tempfile.mkstemp(dir=self.scratch_dir, suffix=".kv")
        with os.fdopen(fd, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())     # defeat write-back caching
        return path

    def fetch(self, handle: Any, device=None) -> Any:
        import jax
        with open(handle, "rb") as f:
            restored = pickle.load(f)
        os.unlink(handle)
        return jax.device_put(restored, device)


PATHS = {"ici": ICIPath, "host": HostPath, "disk": DiskPath}


def make_path(name: str, host: Optional[HostSpec] = None,
              **kw) -> TransferPath:
    return PATHS[name](host=host, **kw)
