"""DNN inference jobs.

A job j is the feedforward computation of a DNN model with L_j layers,
generated at a source node and whose result must be delivered to a
destination node.  ``comp[l]`` (FLOPs) is the load of computing layer l+1
(paper's c_{j,l+1}); ``data[l]`` (bytes) is the output size of layer l
(paper's d_{jl}), with ``data[0]`` the input data size and ``data[L]`` the
inference-result size.

For vmap-friendly multi-job routing, jobs are padded to a common max layer
count in :class:`JobBatch`; padded layers have zero compute and zero data and
are masked out of every cost term.  A batch's leaves live either on the
device (:func:`batch_jobs`) or on the host (:func:`batch_jobs_host`, the
serving scheduler's copy); :func:`host_batch` and :func:`device_batch` move
one to the other only when it is not already there.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import numpy as np

from . import telemetry


@dataclasses.dataclass(frozen=True)
class InferenceJob:
    name: str
    src: int
    dst: int
    comp: np.ndarray  # [L] FLOPs per layer
    data: np.ndarray  # [L+1] bytes: input, per-layer outputs
    # Relative SLO: the job must complete within deadline_s of its arrival
    # (inf = no deadline).  Host-side metadata only — it never enters the
    # JobBatch pytree or any solver cost; the admission layer
    # (repro.serving.admission) is its sole consumer.
    deadline_s: float = float("inf")

    @property
    def num_layers(self) -> int:
        return int(self.comp.shape[0])

    def with_deadline(self, deadline_s: float) -> "InferenceJob":
        return dataclasses.replace(self, deadline_s=float(deadline_s))

    def __post_init__(self):
        # Normalize-then-validate: store the converted arrays so list inputs
        # fail here with a named ValueError, not later with AttributeError.
        comp = np.asarray(self.comp, np.float32)
        data = np.asarray(self.data, np.float32)
        object.__setattr__(self, "comp", comp)
        object.__setattr__(self, "data", data)
        if comp.ndim != 1 or comp.shape[0] < 1:
            raise ValueError(f"comp must be a non-empty [L] vector, got shape {comp.shape}")
        if data.shape != (comp.shape[0] + 1,):
            raise ValueError(
                f"data must have L+1={comp.shape[0] + 1} entries (input + L "
                f"layer outputs), got shape {data.shape}")
        from .validation import check_finite_nonneg
        check_finite_nonneg("comp", comp)
        check_finite_nonneg("data", data)
        if self.src < 0 or self.dst < 0:
            raise ValueError(f"src/dst must be >= 0, got ({self.src}, {self.dst})")
        d = float(self.deadline_s)
        if np.isnan(d) or d <= 0:
            raise ValueError(f"deadline_s must be > 0 (inf = none), got {d}")
        object.__setattr__(self, "deadline_s", d)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class JobBatch:
    """Padded batch of J jobs (a JAX pytree; jax or numpy leaves)."""

    src: jax.Array        # [J] int32
    dst: jax.Array        # [J] int32
    comp: jax.Array       # [J, Lmax] FLOPs (0 beyond L_j)
    data: jax.Array       # [J, Lmax+1] bytes (0 beyond L_j)
    num_layers: jax.Array  # [J] int32

    @property
    def num_jobs(self) -> int:
        return self.src.shape[0]

    @property
    def max_layers(self) -> int:
        return self.comp.shape[1]


def batch_jobs_host(jobs: Sequence[InferenceJob], *,
                    pad_to: int | None = None) -> JobBatch:
    """Pad jobs to a common layer count (``pad_to`` pins the padded width so
    batches of varying composition share one jit shape); numpy leaves."""
    if not jobs:
        raise ValueError("empty job list")
    lmax = max(j.num_layers for j in jobs)
    if pad_to is not None:
        if pad_to < lmax:
            raise ValueError(
                f"pad_to={pad_to} is smaller than the longest job (L={lmax})")
        lmax = pad_to
    J = len(jobs)
    comp = np.zeros((J, lmax), np.float32)
    data = np.zeros((J, lmax + 1), np.float32)
    src = np.zeros((J,), np.int32)
    dst = np.zeros((J,), np.int32)
    nl = np.zeros((J,), np.int32)
    for i, j in enumerate(jobs):
        L = j.num_layers
        comp[i, :L] = j.comp
        data[i, : L + 1] = j.data
        # Padded "layers" carry the final output forward at zero cost: the
        # data entry stays 0 so transfers of padded layers are free and the
        # true final transfer d_L is handled by the masked DP epilogue.
        src[i], dst[i], nl[i] = j.src, j.dst, L
    return JobBatch(src=src, dst=dst, comp=comp, data=data, num_layers=nl)


def batch_jobs(jobs: Sequence[InferenceJob], *, pad_to: int | None = None) -> JobBatch:
    """:func:`batch_jobs_host`, uploaded."""
    return telemetry.to_device(batch_jobs_host(jobs, pad_to=pad_to))


def host_batch(batch: JobBatch) -> JobBatch:
    """``batch`` with numpy leaves: itself when it has them, else one
    fetch, counted as ``batch_fetches`` (a served batch never needs it)."""
    if isinstance(batch.data, np.ndarray):
        return batch
    telemetry.count("batch_fetches")
    return telemetry.to_host(batch)


def device_batch(batch: JobBatch) -> JobBatch:
    """``batch`` with device leaves: itself when it has them, else one
    upload (for code that indexes or jits the batch directly)."""
    if isinstance(batch.data, np.ndarray):
        return telemetry.to_device(batch)
    return batch


def synthetic_job(
    name: str, src: int, dst: int, num_layers: int, *, seed: int = 0,
    flops_scale: float = 1e9, bytes_scale: float = 1e6,
) -> InferenceJob:
    """Random job for property tests / the paper's hand-made third model."""
    rng = np.random.default_rng(seed)
    comp = rng.uniform(0.2, 2.0, size=num_layers).astype(np.float32) * flops_scale
    data = rng.uniform(0.1, 1.5, size=num_layers + 1).astype(np.float32) * bytes_scale
    return InferenceJob(name, src, dst, comp, data)
