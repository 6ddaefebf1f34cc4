"""Device mesh construction.

The single Mesh replaces the reference's three separate transports
(SURVEY.md §5 'Distributed communication backend'): intra-node device
averaging (``Nd4j.averageAndPropagate``, ``ParallelWrapper.java:326``),
Spark tree-aggregation, and the Aeron VoidParameterServer — XLA emits
all-reduce over ICI within a slice and DCN collectives across slices from
the sharding annotations alone.

Axis convention (the full 5-axis layout models shard over):
- "data"     — batch (DP)
- "model"    — tensor parallel (TP) within layers
- "pipe"     — pipeline stages (PP)
- "seq"      — sequence/context parallel (SP, ring attention)
- "expert"   — expert parallel (EP, MoE layers; GSPMD inserts the
               token all-to-all from the expert-dim shardings)
Unused axes are size 1 and cost nothing.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def shard_map(f, mesh, in_specs, out_specs, axis_names=None,
              check_vma=False):
    """``jax.shard_map`` with this package's defaults: ``check_vma``
    off, and ``axis_names`` the set of MANUAL axes (None = all; the
    rest of the mesh stays automatic)."""
    kw = {} if axis_names is None else {"axis_names": set(axis_names)}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kw)


def zero1_donation(*argnums) -> tuple:
    """Buffer donation for a jit step whose ZeRO-1 weight update
    reshards params/opt-state (repl → data-sharded → repl).

    On real accelerators every device owns its memory, so donating the
    params/opt-state inputs is the standard training-loop memory
    optimization and stays on. The CPU backend emulates the mesh with
    virtual devices sharing one host heap; there, donation lets the
    all-gather of the updated shards write into a buffer other virtual
    devices are still reading, silently corrupting results (observed
    nondeterministically on the 8-device test mesh as garbage updater
    slots). Replicated-update steps don't carry that aliasing pattern
    and keep donation unconditionally; ZeRO-1 steps donate through this
    helper: everywhere except CPU."""
    if jax.default_backend() == "cpu":
        return ()
    return tuple(argnums)


class TrainingMesh:
    def __init__(
        self,
        data: int = 0,
        model: int = 1,
        pipe: int = 1,
        seq: int = 1,
        expert: int = 1,
        devices: Optional[Sequence] = None,
    ):
        devices = list(devices if devices is not None else jax.devices())
        n = len(devices)
        if data == 0:
            used = model * pipe * seq * expert
            if n % used:
                raise ValueError(
                    f"{n} devices not divisible by model*pipe*seq*expert={used}"
                )
            data = n // used
        total = data * model * pipe * seq * expert
        if total != n:
            raise ValueError(
                f"mesh {data}x{model}x{pipe}x{seq}x{expert}={total} != {n} devices"
            )
        arr = np.asarray(devices).reshape(data, model, pipe, seq, expert)
        self.mesh = Mesh(arr, ("data", "model", "pipe", "seq", "expert"))
        self.shape: Dict[str, int] = dict(zip(self.mesh.axis_names, arr.shape))

    # -- shardings -----------------------------------------------------------
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def batch_sharded(self) -> NamedSharding:
        return NamedSharding(self.mesh, P("data"))

    def seq_sharded(self) -> NamedSharding:
        """(batch, time, ...) sharded over both data and seq axes."""
        return NamedSharding(self.mesh, P("data", "seq"))

    def spec(self, *axes) -> NamedSharding:
        return NamedSharding(self.mesh, P(*axes))

    @property
    def n_data(self) -> int:
        return self.shape["data"]

    def devices_flat(self) -> list:
        """This mesh's devices in mesh order (data-major)."""
        return list(np.asarray(self.mesh.devices).reshape(-1))

    def shrink(self, survivors: Sequence) -> "TrainingMesh":
        """A data-parallel sub-mesh over ``survivors`` — the elastic
        recovery re-formation (parallel/reshard.py / ElasticFitDriver).
        Only pure-DP meshes shrink freely; TP/PP/SP/EP axes tile the
        model itself, so losing a device there changes the program, not
        just the batch split."""
        others = {k: v for k, v in self.shape.items()
                  if k != "data" and v != 1}
        if others:
            raise ValueError(
                f"cannot shrink a mesh with non-trivial axes {others}: "
                "elastic re-formation is data-parallel only")
        survivors = list(survivors)
        if not survivors:
            raise ValueError("cannot form a mesh from zero survivors")
        return TrainingMesh(data=len(survivors), devices=survivors)

    def __repr__(self):
        return f"TrainingMesh({self.shape})"
