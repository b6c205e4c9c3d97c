"""Unified 2-D device topology for Posterior Propagation.

The paper's headline system composes TWO levels of parallelism:

  * block level — same-phase PP blocks run concurrently (phase-graph
    executors, zero collectives inside a phase);
  * intra-block level — each block's Gibbs chain is itself distributed
    over several workers (ref [16]/[17]: rows of U sharded, item stats
    reduced or factors exchanged each sweep).

``Topology`` is the ONE placement object every executor consumes: a
single 2-D ``('block', 'data')`` mesh whose major axis counts *device
groups* (block-level parallelism) and whose minor axis counts *devices
per group* (intra-block parallelism).

    Topology(block=2, data=2)      # 4 devices: 2 groups of 2
      group 0: devices[0:2]  — runs blocks, each chain sharded 2-way
      group 1: devices[2:4]

Every executor consumes the same object:

  * ``ShardedExecutor``   shard_maps the stacked bucket batch over the
    'block' axis while each block's chain runs the intra-block
    distributed sweep over the 'data' axis
    (``distributed.run_gibbs_stacked_2d``);
  * ``AsyncExecutor``     stages each ready block on the least-loaded
    group — a dispatch lands on a whole group and the chain is
    'data'-sharded inside it;
  * ``StreamingExecutor`` keeps one W-bounded donated window per group
    (per-stream prefetch), dispatching each chunk onto its group;
  * ``SerialExecutor``    runs one block at a time on the single group
    (``block == 1``), its chain 'data'-sharded like an async dispatch.

Multi-host block placement then becomes a config change — a Topology
over ``jax.devices()`` spanning hosts — rather than a new executor.

Mesh axis names are the repo-wide contract: 'block' collectives are
forbidden (phase boundaries go through the posterior store), 'data'
collectives are the intra-block sweep's limited communication
(``launch.bmf_dryrun --pp-engine`` lowers the composed executable and
asserts exactly that split from the HLO replica groups).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh

BLOCK_AXIS = "block"
DATA_AXIS = "data"


@dataclass(frozen=True)
class Topology:
    """Placement of the PP run on ``block × data`` devices.

    block:   device groups — how many blocks run concurrently.
    data:    devices per group — how many shards inside one block's chain.
    devices: explicit device sequence (length block*data, grouped
             row-major: group g = devices[g*data:(g+1)*data]); defaults
             to the first ``block * data`` local devices.
    """
    block: int = 1
    data: int = 1
    devices: Optional[Tuple] = None

    def __post_init__(self):
        if self.block < 1 or self.data < 1:
            raise ValueError(f"topology axes must be >= 1, got "
                             f"block={self.block} data={self.data}")
        devs = (tuple(self.devices) if self.devices is not None
                else tuple(jax.devices()[: self.block * self.data]))
        if len(devs) != self.block * self.data:
            raise ValueError(
                f"topology {self.block}x{self.data} needs "
                f"{self.block * self.data} devices, got {len(devs)}")
        object.__setattr__(self, "devices", devs)

    # -- constructors -------------------------------------------------------

    @staticmethod
    def default(data: int = 1) -> "Topology":
        """All local devices, ``data`` per group (block = n_devices/data)."""
        n = len(jax.devices())
        if n % data:
            raise ValueError(f"{n} devices not divisible by data={data}")
        return Topology(block=n // data, data=data)

    @staticmethod
    def from_spec(spec) -> "Topology":
        """Coerce run_pp-style specs: a Topology, None (all devices,
        data=1), an ``(block, data)`` pair, an explicit device sequence
        (one single-device group per device), or a Mesh with axes
        ('block',), ('data',) or ('block', 'data')."""
        if spec is None:
            return Topology.default()
        if isinstance(spec, Topology):
            return spec
        if (isinstance(spec, (list, tuple)) and spec
                and not all(isinstance(x, (int, np.integer)) for x in spec)):
            devs = tuple(spec)
            return Topology(block=len(devs), data=1, devices=devs)
        if isinstance(spec, Mesh):
            names = tuple(spec.axis_names)
            devs = tuple(spec.devices.flat)
            if names == (BLOCK_AXIS,):
                return Topology(block=len(devs), data=1, devices=devs)
            if names == (DATA_AXIS,):
                return Topology(block=1, data=len(devs), devices=devs)
            if names == (BLOCK_AXIS, DATA_AXIS):
                b, d = spec.devices.shape
                return Topology(block=b, data=d, devices=devs)
            raise ValueError(f"mesh axes {names} are not a PP topology "
                             f"(expected ('block',), ('data',) or "
                             f"('block','data'))")
        b, d = spec
        return Topology(block=int(b), data=int(d))

    # -- derived meshes -----------------------------------------------------

    @property
    def n_devices(self) -> int:
        return self.block * self.data

    @property
    def mesh(self) -> Mesh:
        """The full 2-D ('block', 'data') mesh."""
        grid = np.asarray(self.devices, dtype=object).reshape(
            self.block, self.data)
        return Mesh(grid, (BLOCK_AXIS, DATA_AXIS))

    def block_mesh(self) -> Mesh:
        """1-D 'block' mesh over the devices — the sharded executor's
        inter-block mesh (``distributed.make_block_mesh``); only meaningful
        at data == 1."""
        if self.data != 1:
            raise ValueError(
                f"block_mesh() is the data==1 degenerate form; this "
                f"topology has data={self.data} (use .mesh)")
        return Mesh(np.asarray(self.devices, dtype=object), (BLOCK_AXIS,))

    def group(self, g: int) -> Tuple:
        """Devices of group ``g`` (one intra-block 'data' stream)."""
        return self.devices[g * self.data:(g + 1) * self.data]

    def groups(self) -> Tuple[Tuple, ...]:
        """All device groups, in block-axis order."""
        return tuple(self.group(g) for g in range(self.block))

    def group_mesh_2d(self, g: int = 0) -> Mesh:
        """(1, data) submesh of group ``g`` with BOTH axis names — lets the
        stacked 2-D chain executable serve single-group dispatches (async
        groups, streaming windows) unchanged."""
        grid = np.asarray(self.group(g), dtype=object).reshape(1, self.data)
        return Mesh(grid, (BLOCK_AXIS, DATA_AXIS))

    def without_groups(self, dead) -> "Topology":
        """The surviving sub-topology after dropping device groups
        ``dead`` (e.g. ``TopologyDegradedError.dead_groups``) — same
        ``data`` width, the remaining groups in canonical order. Block
        posteriors are placement-independent, so a run checkpointed
        before the degradation resumes bitwise-identically on the
        survivor topology."""
        dead = {int(g) for g in dead}
        bad = dead - set(range(self.block))
        if bad:
            raise ValueError(f"unknown group(s) {sorted(bad)} "
                             f"(topology has {self.block} group(s))")
        alive = [g for g in range(self.block) if g not in dead]
        if not alive:
            raise ValueError("cannot drop every device group")
        devs = tuple(d for g in alive for d in self.group(g))
        return Topology(block=len(alive), data=self.data, devices=devs)

    def describe(self) -> str:
        return (f"topology {self.block}x{self.data} "
                f"({self.block} group(s) x {self.data} device(s))")
