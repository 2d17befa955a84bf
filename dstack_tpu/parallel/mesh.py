"""Device-mesh construction for TPU pod slices.

The control plane provisions TPU slices with a physical ICI topology (e.g.
``v5e-64`` as a 4-host slice); the compute layer maps that hardware onto a
logical `jax.sharding.Mesh` with named axes:

- ``dcn``    — data parallelism *across pod slices* (multislice): gradient
               all-reduce rides the data-center network via MEGASCALE_*
               coupling; always the slowest-varying axis.
- ``data``   — pure data parallelism within a slice (ICI).
- ``fsdp``   — fully-sharded data parallelism (params/opt-state sharded,
               all-gathered per layer; keep on ICI).
- ``tensor`` — tensor/model parallelism over the MXU contraction dims (must be
               on ICI; typically <= 8).
- ``seq``    — sequence/context parallelism for long-context ring attention.
- ``expert`` — expert parallelism for MoE layers.
- ``stage``  — pipeline parallelism (GPipe microbatch schedule over ppermute;
               see `parallel/pipeline.py`). Slow-varying: stage hand-off is
               one neighbour hop per microbatch, so it tolerates DCN.

Reference parity: dstack's runner only *bootstraps* NCCL rendezvous
(``runner/internal/runner/executor/executor.go:480-494``) and leaves layout to
user code; here the mesh is a first-class framework object that the serving
and training stacks consume directly.

These axis names are LINT-ENFORCED: shardlint (the DT6xx families of
``python -m dstack_tpu.analysis``) resolves every collective's
``axis_name`` and every ``P(...)`` spec interprocedurally and fails CI
when a name is not in :data:`AXIS_ORDER` — the set is read from THIS
module at scan time, so adding an axis here automatically teaches the
linter.  See ``docs/contributing/static-analysis.md`` ("SPMD rules
(DT6xx)") for the per-rule incident rationale.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

DCN = "dcn"
STAGE = "stage"
DATA = "data"
FSDP = "fsdp"
TENSOR = "tensor"
SEQ = "seq"
EXPERT = "expert"

#: Canonical axis order: slowest-varying (DCN) first, ICI-local last.
AXIS_ORDER = (DCN, STAGE, DATA, FSDP, EXPERT, SEQ, TENSOR)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical parallelism layout. Product of sizes must equal device count."""

    dcn: int = 1   # number of slices (multislice over DCN)
    stage: int = 1
    data: int = 1
    fsdp: int = 1
    tensor: int = 1
    seq: int = 1
    expert: int = 1

    @property
    def sizes(self) -> dict[str, int]:
        return {
            DCN: self.dcn,
            STAGE: self.stage,
            DATA: self.data,
            FSDP: self.fsdp,
            EXPERT: self.expert,
            SEQ: self.seq,
            TENSOR: self.tensor,
        }

    @property
    def num_devices(self) -> int:
        return math.prod(self.sizes.values())

    def axis_names(self) -> tuple[str, ...]:
        return AXIS_ORDER

    @staticmethod
    def auto(
        n_devices: int,
        *,
        tensor: Optional[int] = None,
        seq: int = 1,
        data: int = 1,
        dcn: int = 1,
        stage: int = 1,
    ) -> "MeshSpec":
        """Pick a sensible default layout: given optional tensor/seq/data/dcn/
        stage degrees, put all remaining parallelism on ``fsdp``.  ``dcn``
        should be the number of slices (MEGASCALE_NUM_SLICES) so cross-slice
        traffic is pure gradient all-reduce.
        """
        tensor = tensor or 1
        used = tensor * seq * data * dcn * stage
        if n_devices % used != 0:
            raise ValueError(
                f"n_devices={n_devices} not divisible by "
                f"tensor*seq*data*dcn*stage={used}"
            )
        return MeshSpec(dcn=dcn, stage=stage, data=data,
                        fsdp=n_devices // used, tensor=tensor, seq=seq)


def shrink_spec(spec: MeshSpec, n_devices: int) -> MeshSpec:
    """Recompute ``spec`` for a smaller (or larger) surviving device count.

    Elastic re-meshing after a host loss or slice shrink: the axes that
    change the *program* (``tensor``/``seq``/``stage`` — they shard weight
    contraction dims, sequence blocks, and pipeline stages) are preserved,
    and the pure data-parallel axes (``dcn``/``data``/``expert``/``fsdp``)
    fold into whatever the survivors support: ``data`` and ``expert``
    shrink first (largest divisor of the remainder that still divides
    their old degree), everything left goes to ``fsdp``.  The restored
    train state then reshards onto the new mesh (`train.resume_train_state`)
    with no change to model semantics — only gradient batch math moves.

    Raises ValueError when ``n_devices`` cannot host the preserved axes.
    """
    if n_devices <= 0:
        raise ValueError(f"n_devices must be positive, got {n_devices}")
    fixed = spec.tensor * spec.seq * spec.stage
    if n_devices % fixed != 0:
        raise ValueError(
            f"{n_devices} surviving devices cannot keep tensor={spec.tensor} "
            f"x seq={spec.seq} x stage={spec.stage} (= {fixed}); shrink one "
            "of the model-topology axes explicitly"
        )
    remaining = n_devices // fixed

    def take(old: int) -> int:
        """Largest divisor of ``remaining`` that also divides ``old``."""
        d = math.gcd(remaining, old)
        return d

    data = take(spec.data)
    remaining //= data
    expert = take(spec.expert)
    remaining //= expert
    return MeshSpec(
        dcn=1, stage=spec.stage, data=data, fsdp=remaining,
        tensor=spec.tensor, seq=spec.seq, expert=expert,
    )


def multislice_spec(n_devices: int, **kw) -> MeshSpec:
    """MeshSpec.auto with ``dcn`` taken from MEGASCALE_NUM_SLICES env (set by
    the runner agent for multislice jobs) — the one-call path for user code
    running under the control plane."""
    import os

    dcn = int(os.environ.get("MEGASCALE_NUM_SLICES", "1"))
    return MeshSpec.auto(n_devices, dcn=dcn, **kw)


def build_mesh(
    spec: MeshSpec,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a Mesh with classic (Auto) axis semantics.

    Devices are laid out so the fastest-varying logical axis (``tensor``)
    maps to adjacent device ids — on a real slice, adjacent ids are ICI
    neighbours, so tensor-parallel collectives ride the fastest links.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if spec.num_devices != n:
        raise ValueError(
            f"MeshSpec wants {spec.num_devices} devices, have {n}: {spec}"
        )
    shape = tuple(spec.sizes[a] for a in AXIS_ORDER)
    dev_array = np.asarray(devices).reshape(shape)
    axis_types = (jax.sharding.AxisType.Auto,) * len(AXIS_ORDER)
    return Mesh(dev_array, AXIS_ORDER, axis_types=axis_types)


def local_mesh(spec: Optional[MeshSpec] = None) -> Mesh:
    """Mesh over whatever devices this process sees (single host / tests)."""
    devices = jax.devices()
    if spec is None:
        spec = MeshSpec.auto(len(devices))
    return build_mesh(spec, devices)
