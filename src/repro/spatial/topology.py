"""Device-mesh topology for the sequence-sharded serving runtime.

A ``ShardTopology`` describes the ring of compute units one request is
sharded across: which jax devices back the shards, the 1-axis mesh the
engine's shard_map dispatches run over, and the page -> shard ownership
map. Pages are STRIPED (global logical page ``j`` lives on shard
``j % n_shards``) so every shard holds ~1/N of any sequence's context —
decode load stays balanced no matter how a prompt grows, and the DLZS
tile grid (pages) aligns with shard boundaries by construction, the
cross-stage tiling requirement carried up to the spatial layer.

The physical communication story mirrors the paper's §V-B: on a torus
interconnect (TPU ICI) the partial-softmax merge is a free logical ring
(ppermute / psum); on a wrap-around-free 2D-mesh NoC the same ring is
realized by MRCA (core/mrca.py). ``neighbor_schedule`` exposes the
MRCA-derived per-step send lists so the engine and the spatial
benchmarks can cost the exchange on either fabric; the host harness
("fake devices" via ``xla_force_host_platform_device_count``) executes
the merge as the psum tree, which is schedule-equivalent (every shard's
partial reaches the owner exactly once).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

from repro.core import mrca

FORCE_FLAG = "--xla_force_host_platform_device_count"


def cpu_only() -> bool:
    """True when ``JAX_PLATFORMS`` pins JAX to the CPU. Read from the
    environment, so asking initializes no backend and holds no chip."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def ensure_host_devices(n: int) -> None:
    """Request ``n`` fake host devices. MUST run before the first jax
    import of the process — XLA fixes the device count at first init, so
    multi-shard drivers (tests/benchmarks) spawn subprocesses that call
    this at the very top. Only the CPU platform reads the flag."""
    flags = os.environ.get("XLA_FLAGS", "")
    if FORCE_FLAG not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {FORCE_FLAG}={n}".strip()


def _respawn_with_devices(n: int, argv: list, *, cwd: Optional[str] = None,
                          guard: str = "_REPRO_SPATIAL_CHILD") -> int:
    """Re-execute ``sys.executable + argv`` in a child with ``n`` forced
    fake host devices; returns the child's exit code. CPU only: the
    parent's device count cannot grow after jax initialized, and on an
    accelerator the parent holds the chip, so a child could not get it.
    ``guard`` is an env marker that stops an infinite respawn loop if
    forcing has no effect (e.g. XLA_FLAGS overridden downstream)."""
    import subprocess
    import sys

    if os.environ.get(guard):
        raise SystemExit(
            f"fake-device respawn failed: child still has fewer than {n} "
            f"devices (is XLA_FLAGS being overridden?)")
    env = dict(os.environ)
    env["XLA_FLAGS"] = \
        f"{env.get('XLA_FLAGS', '')} {FORCE_FLAG}={n}".strip()
    env[guard] = "1"
    return subprocess.call([sys.executable] + list(argv), env=env, cwd=cwd)


def require_devices(n: int, argv: list, *,
                    cwd: Optional[str] = None) -> None:
    """Make sure this process sees ``n`` devices for an ``n``-shard mesh.

    With ``JAX_PLATFORMS=cpu`` and too few devices, re-execute ``argv``
    with ``n`` fake host devices and exit with the child's status.
    Anywhere else never respawn — one process per chip — and fail with a
    clear message when JAX sees fewer than ``n`` devices."""
    import sys

    import jax
    have = len(jax.devices())
    if have >= n:
        return
    if cpu_only():
        sys.exit(_respawn_with_devices(n, argv, cwd=cwd))
    raise SystemExit(
        f"{n} shards need {n} devices; JAX sees {have} "
        f"{jax.devices()[0].platform} device(s). Run on a host with {n} "
        f"chips, or set JAX_PLATFORMS=cpu for the fake-device harness.")


@dataclasses.dataclass(frozen=True)
class ShardTopology:
    n_shards: int
    axis: str = "shards"

    def __post_init__(self):
        if self.n_shards < 1:
            raise ValueError(f"need >= 1 shard, got {self.n_shards}")

    # -- page ownership (striping) -------------------------------------------

    def owner(self, logical_page: int) -> int:
        """Shard owning global logical page ``logical_page``."""
        return logical_page % self.n_shards

    def local_count(self, n_pages: int, shard: int) -> int:
        """How many of global pages [0, n_pages) land on ``shard``."""
        return (n_pages - shard + self.n_shards - 1) // self.n_shards

    def max_local_count(self, n_pages: int) -> int:
        return self.local_count(n_pages, 0) if n_pages else 0

    # -- jax mesh ------------------------------------------------------------

    def make_mesh(self, devices: Optional[list] = None):
        """1-axis jax mesh over the first ``n_shards`` devices.

        Raises with a pointer to ``ensure_host_devices`` when the process
        has fewer devices than shards — the fake-device harness must be
        set up before jax initializes.
        """
        import jax
        from jax.sharding import Mesh
        import numpy as np

        devices = devices if devices is not None else list(jax.devices())
        if len(devices) < self.n_shards:
            raise RuntimeError(
                f"{self.n_shards}-shard topology needs {self.n_shards} "
                f"devices; this process has {len(devices)}. Set XLA_FLAGS="
                f"{FORCE_FLAG}={self.n_shards} (topology.ensure_host_devices"
                ") before the first jax import, or run on real hardware.")
        return Mesh(np.array(devices[:self.n_shards]), (self.axis,))

    # -- communication schedule ----------------------------------------------

    def neighbor_schedule(self) -> list[list[mrca.Send]]:
        """MRCA per-step neighbor sends realizing the partial-state ring on
        a wrap-around-free 1-D mesh (paper Alg. 1). Used by the spatial
        benchmarks to cost the exchange; the shard_map execution path uses
        the torus-native psum tree instead."""
        if self.n_shards == 1:
            return []
        return mrca.mrca_schedule(self.n_shards)

    def exchange_cost(self, hop_ns: float = 20.0,
                      chunk_bytes: float = 1.0) -> dict:
        """Latency/traffic of the MRCA exchange vs the naive forced ring."""
        if self.n_shards == 1:
            return {"mrca": {"latency_ns": 0.0, "hops": 0, "bytes": 0.0},
                    "naive_ring": {"latency_ns": 0.0, "hops": 0,
                                   "bytes": 0.0}}
        return {
            "mrca": mrca.schedule_cost(self.neighbor_schedule(), hop_ns,
                                       chunk_bytes),
            "naive_ring": mrca.schedule_cost(
                mrca.naive_ring_schedule(self.n_shards), hop_ns,
                chunk_bytes),
        }
