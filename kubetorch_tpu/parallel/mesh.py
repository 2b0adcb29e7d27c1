"""Device-mesh construction from a declarative spec.

Design: the user (or ``Compute.distribute``) states logical axis sizes; we
validate them against the device count, lay the axes out so the
highest-traffic axis (tensor) maps to the innermost/fastest ICI dimension, and
return a ``jax.sharding.Mesh``. Multi-slice TPU pods add a leading ``dcn``
axis (data parallelism across slices rides DCN; everything else stays inside
a slice on ICI) — the megascale recipe from the scaling book.

Axis conventions (all optional, size-1 axes are dropped from PartitionSpecs
automatically by GSPMD):

- ``data``:    pure data parallelism (gradient psum only)
- ``fsdp``:    data parallelism with parameter/optimizer sharding (ZeRO-3);
               params all-gathered per layer, grads reduce-scattered
- ``tensor``:  Megatron-style tensor parallelism within attention/FFN
- ``context``: sequence/context parallelism (ring attention over ICI neighbors)
- ``expert``:  expert parallelism for MoE (all-to-all token routing)
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

AXIS_DCN = "dcn"
AXIS_DATA = "data"
AXIS_FSDP = "fsdp"
AXIS_PIPE = "pipe"
AXIS_TENSOR = "tensor"
AXIS_CONTEXT = "context"
AXIS_EXPERT = "expert"

# Outer-to-inner order: dcn crosses slices (slowest fabric), tensor innermost
# (most collective traffic per step → nearest-neighbor ICI links). Pipe sits
# between the data-like axes and the per-stage axes: one ppermute per
# microbatch per boundary is far less traffic than tensor's per-matmul psums.
CANONICAL_ORDER: Tuple[str, ...] = (
    AXIS_DCN, AXIS_DATA, AXIS_FSDP, AXIS_PIPE, AXIS_EXPERT, AXIS_CONTEXT,
    AXIS_TENSOR,
)


@dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh: axis name → size. ``-1`` on at most one axis means
    "absorb all remaining devices" (like a reshape wildcard)."""

    data: int = 1
    fsdp: int = 1
    pipe: int = 1
    tensor: int = 1
    context: int = 1
    expert: int = 1
    dcn: int = 1  # number of slices (multi-slice pods)

    @classmethod
    def from_dict(cls, d: Dict[str, int]) -> "MeshSpec":
        unknown = set(d) - {a for a in CANONICAL_ORDER}
        if unknown:
            raise ValueError(f"Unknown mesh axes {sorted(unknown)}; valid: {CANONICAL_ORDER}")
        return cls(**{k: int(v) for k, v in d.items()})

    def axis_sizes(self) -> Dict[str, int]:
        return {a: getattr(self, a) for a in CANONICAL_ORDER}

    def resolve(self, n_devices: int) -> "MeshSpec":
        """Fill a single ``-1`` wildcard and validate the product."""
        sizes = self.axis_sizes()
        wild = [a for a, s in sizes.items() if s == -1]
        if len(wild) > 1:
            raise ValueError("At most one mesh axis may be -1")
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if wild:
            if n_devices % fixed:
                raise ValueError(
                    f"Cannot absorb remainder: {n_devices} devices not divisible by {fixed}")
            sizes[wild[0]] = n_devices // fixed
        total = math.prod(sizes.values())
        if total != n_devices:
            raise ValueError(
                f"Mesh spec {sizes} wants {total} devices but {n_devices} are available")
        return MeshSpec(**sizes)

    @property
    def names(self) -> Tuple[str, ...]:
        return CANONICAL_ORDER

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(getattr(self, a) for a in CANONICAL_ORDER)

    def shrink_to(self, n_devices: int) -> "MeshSpec":
        """Re-mesh for a smaller device count (elastic N-1 resume,
        ISSUE 6 / NTP arXiv:2504.06095's degraded-but-alive mode).

        Model-parallel axes (tensor/context/expert/pipe) keep their sizes —
        they define the sharded program's shape and the checkpoint's leaf
        layout — while the data-like axes (data, then fsdp, then dcn, in
        shrink-preference order) absorb the loss: pure data parallelism
        costs only throughput to shrink, fsdp additionally re-gathers
        parameters (the resharded checkpoint load handles that), and
        slice count moves last. Raises ``ValueError`` when ``n_devices``
        cannot hold the model axes at all.
        """
        sizes = self.axis_sizes()
        data_axes = (AXIS_DATA, AXIS_FSDP, AXIS_DCN)
        model = math.prod(s for a, s in sizes.items() if a not in data_axes)
        if n_devices < model or n_devices % model:
            raise ValueError(
                f"Cannot re-mesh to {n_devices} devices: model-parallel "
                f"axes need a multiple of {model}")
        for axis in data_axes:
            trial = dict(sizes)
            trial[axis] = -1
            try:
                return MeshSpec(**trial).resolve(n_devices)
            except ValueError:
                continue
        # remainder doesn't factor across the kept data axes: collapse all
        # data parallelism onto one axis (prefer fsdp if it was in use)
        trial = dict(sizes)
        trial.update({a: 1 for a in data_axes})
        trial[AXIS_FSDP if sizes[AXIS_FSDP] > 1 else AXIS_DATA] = -1
        return MeshSpec(**trial).resolve(n_devices)


@dataclass
class DistributedConfig:
    """The ``.distribute()`` payload that travels controller→pod as metadata.

    Reference analog: ``Compute.distributed_config`` (``compute.py:1570-1604``)
    which carried only {type, workers, procs}. Ours adds the mesh.
    """

    distribution_type: str = "jax"      # jax | pytorch | tensorflow | ray | spmd | local
    workers: int = 1                    # pod replicas (hosts)
    procs_per_worker: Optional[int] = None  # default: 1 per TPU host (megacore)
    mesh: Optional[Dict[str, int]] = None
    restart_procs: bool = False
    # elastic policy knobs (serving/elastic.py ElasticPolicy.from_dict):
    # present → rank loss resumes from the last committed checkpoint on a
    # re-meshed N-1 world instead of cancelling the fan-out. {} opts in
    # with every default.
    elastic: Optional[Dict] = None

    def to_dict(self) -> Dict:
        return {
            "distribution_type": self.distribution_type,
            "workers": self.workers,
            "procs_per_worker": self.procs_per_worker,
            "mesh": self.mesh,
            "restart_procs": self.restart_procs,
            "elastic": self.elastic,
        }

    @classmethod
    def from_dict(cls, d: Dict) -> "DistributedConfig":
        return cls(**{k: d.get(k) for k in (
            "distribution_type", "workers", "procs_per_worker", "mesh",
            "restart_procs", "elastic")
            if d.get(k) is not None})


def build_mesh(spec: MeshSpec | Dict[str, int] | None = None,
               devices: Optional[Sequence] = None):
    """Construct a ``jax.sharding.Mesh`` from a spec.

    Devices are laid out in canonical order so ``tensor`` varies fastest. On
    TPU ``jax.experimental.mesh_utils`` maps the axes onto the physical torus
    (tensor neighbors one ICI hop apart) and a shape it cannot place raises;
    other platforms have no topology and reshape in enumeration order.
    """
    import jax
    from jax.sharding import Mesh
    import numpy as np

    if devices is None:
        devices = jax.devices()
    if spec is None:
        spec = MeshSpec(data=len(devices))
    if isinstance(spec, dict):
        spec = MeshSpec.from_dict(spec)
    spec = spec.resolve(len(devices))

    shape = spec.shape
    if devices[0].platform == "tpu":
        from jax.experimental import mesh_utils
        dev_array = mesh_utils.create_device_mesh(shape, devices=list(devices))
    else:
        dev_array = np.asarray(list(devices)).reshape(shape)
    return Mesh(dev_array, spec.names)


def live_axes(mesh) -> Dict[str, int]:
    """Axis name → size for every mesh axis with size > 1 (the axes that
    actually shard anything; size-1 axes are pruned from PartitionSpecs)."""
    return {n: s for n, s in zip(mesh.axis_names, mesh.devices.shape) if s > 1}


def normalize_batch_axes(live: Dict[str, int],
                         batch_axes: Sequence[str] = ("dcn", "data", "fsdp")):
    """Batch-dim PartitionSpec entry from the live axes: a tuple when
    several batch axes shard it, the bare name for one, None for none —
    the one normalization every shard_map spec builder and cache-sharding
    site shares (drift here desynchronizes specs from stored layouts and
    forces reshards)."""
    ba = tuple(a for a in batch_axes if a in live)
    return ba if len(ba) > 1 else (ba[0] if ba else None)


def fit_batch_axes(live: Dict[str, int], dim: int,
                    batch_axes: Sequence[str] = ("dcn", "data", "fsdp")):
    """Batch-dim PartitionSpec entry for a dim of size ``dim``: the largest
    prefix of the live batch axes whose total size divides it. An explicit
    sharding must divide evenly — ``device_put`` and ``shard_map`` do not
    pad the way GSPMD does."""
    axes = tuple(a for a in batch_axes if a in live)
    while axes and dim % math.prod(live[a] for a in axes):
        axes = axes[:-1]
    return normalize_batch_axes(live, axes)


def best_mesh_for(n_devices: int, prefer: str = "fsdp") -> MeshSpec:
    """A sensible default mesh when the user gives none: everything on one
    axis (fsdp by default — params shard, no user model change needed)."""
    return MeshSpec(**{prefer: n_devices})
