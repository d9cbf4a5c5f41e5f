"""Device mesh construction and sharding policy.

Replaces the reference's AffinityManager device placement (SURVEY.md
§2.10) with explicit ``jax.sharding.Mesh`` axes.  Axis names follow the
scaling-book convention: 'data' (dp), 'fsdp' (zero-style param sharding),
'model' (tp), 'seq' (sp), 'expert' (ep) — a config picks which are used;
unused axes have size 1 so one code path serves every layout.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXES = ("data", "fsdp", "model", "seq", "expert")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """How many devices along each named axis (product must divide the
    device count; -1 on 'data' means 'all remaining')."""

    data: int = -1
    fsdp: int = 1
    model: int = 1
    seq: int = 1
    expert: int = 1

    def resolve(self, n_devices: int) -> Tuple[int, int, int, int, int]:
        fixed = self.fsdp * self.model * self.seq * self.expert
        data = self.data
        if data == -1:
            if n_devices % fixed:
                raise ValueError(f"{n_devices} devices not divisible by {fixed}")
            data = n_devices // fixed
        if data * fixed != n_devices:
            raise ValueError(
                f"mesh {data}x{fixed} != {n_devices} devices")
        return (data, self.fsdp, self.model, self.seq, self.expert)


def make_mesh(config: Optional[MeshConfig] = None,
              devices: Optional[Sequence] = None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    config = config or MeshConfig()
    shape = config.resolve(len(devices))
    arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, AXES)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def data_sharded(mesh: Mesh) -> NamedSharding:
    """Batch-dim sharding over data(+fsdp) — the standard input layout."""
    return NamedSharding(mesh, P(("data", "fsdp")))


def param_sharding(mesh: Mesh, arr_shape: Tuple[int, ...],
                   replicate_below: int = 0) -> NamedSharding:
    """Parameter layout over the mesh:

    * arrays with fewer than ``replicate_below`` elements (biases, BN
      stats, LayerNorm scales) are REPLICATED outright: sharding a
      few-KB vector buys nothing and costs an all-gather per step
      (the ZeRO paper's small-tensor exemption, arXiv 2004.13336 §4).
    * 'model' (tensor parallelism): the LAST axis of ≥2-D params (a
      matmul's output features) shards over 'model' — GSPMD then
      partitions the matmuls and inserts the activation collectives
      (Megatron column-parallel layout, scaling-book recipe).
    * 'expert' (MoE): the FIRST axis of ≥3-D params shards over
      'expert' — expert weight stacks are [E, in, out]
      (MixtureOfExperts layer), and GSPMD turns the dispatch/combine
      einsums into expert-parallel all-to-alls.  The ndim≥3 gate keeps
      plain [in, out] matrices (whose fan-in merely happens to divide E)
      replicated.
    * 'fsdp' (ZeRO): the largest remaining divisible axis shards over
      'fsdp'.
    * 'data': always replicated.
    """
    if replicate_below and int(np.prod(arr_shape or (1,))) < replicate_below:
        return NamedSharding(mesh, P())
    fsdp = mesh.shape["fsdp"]
    model = mesh.shape["model"]
    expert = mesh.shape["expert"]
    spec = [None] * len(arr_shape)
    if expert > 1 and len(arr_shape) >= 3 and arr_shape[0] % expert == 0:
        spec[0] = "expert"
    if (model > 1 and len(arr_shape) >= 2 and spec[-1] is None
            and arr_shape[-1] % model == 0):
        spec[-1] = "model"
    if fsdp > 1:
        best = None
        for i, d in enumerate(arr_shape):
            if spec[i] is None and d % fsdp == 0 and (
                    best is None or d > arr_shape[best]):
                best = i
        if best is not None:
            spec[best] = "fsdp"
    return NamedSharding(mesh, P(*spec))
