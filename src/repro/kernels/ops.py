"""Jit'd public wrappers around the Pallas kernels with backend dispatch.

On TPU the Pallas path runs compiled; everywhere else (CPU CI, the
dry-run's 512 fake host devices) the jnp reference executes — identical
math, so tests interchange them freely. ``interpret=True`` forces the
Pallas kernel body through the interpreter for correctness validation on
CPU (this is how tests/test_kernels.py sweeps shapes/dtypes).

Pruned-DMA note: `distance_topk` has two pruning levels. ``impl="pallas"``
takes the PGBJ visit mask per tile and `pl.when` elides the tile's
*compute* — its HBM→VMEM stream still runs. ``impl="gather"`` runs the
real `distance_topk_gather` kernel: a scalar-prefetch grid
(PrefetchScalarGridSpec) reads each step's S-tile index from the
compacted schedule that `core.schedule.build_tile_schedule` lowers from
the plan's bounds, so pruned tiles are never DMA'd at all — zero bytes,
zero FLOPs. ``impl="gather_interpret"`` pushes the same kernel body
through the interpreter (CPU validation), and
`ref.distance_topk_gather_ref` is the jnp oracle for both.

Serving note: the brute-force ``distance_topk`` path is what
`serve.retrieval.knn_logits(use_kernel=True)` runs over the SIndex's
device-resident pivot-sorted rows (`SIndex.device_rows`) — local row
ids map back to global ones via ``s_ids_sorted``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref
from .assign import assign_pallas
from .distance_topk import distance_topk_gather_pallas, distance_topk_pallas
from .flash_attention import flash_attention_pallas
from .quant_topk import quant_coarse_gather_pallas

__all__ = ["distance_topk", "quant_coarse_topk", "assign",
           "flash_attention", "use_pallas"]


def use_pallas() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("k", "bm", "bn", "impl"))
def distance_topk(
    r: jnp.ndarray, s: jnp.ndarray, k: int,
    *, visit_mask: Optional[jnp.ndarray] = None,
    schedule: Optional[jnp.ndarray] = None,
    counts: Optional[jnp.ndarray] = None,
    alive: Optional[jnp.ndarray] = None,
    bm: int = 128, bn: int = 512, impl: str = "auto",
):
    """k nearest rows of s per row of r → (dists ascending, ids int32).

    impl="gather" / "gather_interpret" run the pruned-schedule kernel and
    require ``schedule`` (nr_tiles, max_visits) + ``counts`` (nr_tiles,);
    impl="gather_ref" is its jnp oracle. ``alive`` (optional (n_s,)
    float32 row mask, >0 = live) masks tombstoned / padding rows on the
    gather impls — the megastep's concatenated multi-segment layout.
    Other impls ignore schedule/counts/alive.
    """
    impl = ("pallas" if use_pallas() else "ref") if impl == "auto" else impl
    if impl == "ref":
        return ref.distance_topk_ref(r, s, k)
    if impl in ("gather", "gather_interpret", "gather_ref"):
        if schedule is None or counts is None:
            raise ValueError(f"impl={impl!r} requires schedule and counts")
        if impl == "gather_ref":
            return ref.distance_topk_gather_ref(
                r, s, k, schedule, counts, bm=bm, bn=bn, alive=alive)
        return distance_topk_gather_pallas(
            r, s, k, schedule, counts, alive=alive, bm=bm, bn=bn,
            interpret=impl == "gather_interpret")[:2]
    return distance_topk_pallas(
        r, s, k, visit_mask=visit_mask, bm=bm, bn=bn,
        interpret=impl == "interpret")[:2]


@functools.partial(jax.jit, static_argnames=("mp", "bm", "bn", "impl"))
def quant_coarse_topk(
    qi: jnp.ndarray, qscale: jnp.ndarray, qeps: jnp.ndarray,
    theta: jnp.ndarray, si: jnp.ndarray, sscale: jnp.ndarray,
    seps: jnp.ndarray, alive: jnp.ndarray, mp: int,
    *, schedule: Optional[jnp.ndarray] = None,
    counts: Optional[jnp.ndarray] = None,
    bm: int = 128, bn: int = 512, impl: str = "auto",
):
    """Int8 coarse shortlist for the quantized tier (`repro.quant`):
    ascending certified lower bounds + packed row positions, (n, mp).

    impl="pallas"/"pallas_interpret" run the schedule-driven gather
    kernel (requires ``schedule`` + ``counts``; int8 tiles are the only
    bytes streamed); impl="ref_sched" is its schedule-consuming scan
    twin (same visit list, CPU validation); impl="ref" is the dense jnp
    oracle (ignores the schedule — a sound candidate superset). The
    shortlist is NOT a result: callers must re-rank it with exact fp32
    distances and certify the exclusion (see `repro.quant.engine`).
    """
    impl = ("pallas" if use_pallas() else "ref") if impl == "auto" else impl
    if impl == "ref":
        return ref.quant_coarse_topk_ref(
            qi, qscale, qeps, theta, si, sscale, seps, alive, mp, bn=bn)
    if impl in ("pallas", "pallas_interpret", "ref_sched"):
        if schedule is None or counts is None:
            raise ValueError(f"impl={impl!r} requires schedule and counts")
        if impl == "ref_sched":
            return ref.quant_coarse_sched_ref(
                qi, qscale, qeps, theta, si, sscale, seps, alive, mp,
                schedule, counts, bm=bm, bn=bn)
        return quant_coarse_gather_pallas(
            qi, qscale, qeps, theta, si, sscale, seps, alive, mp,
            schedule, counts, bm=bm, bn=bn,
            interpret=impl == "pallas_interpret")
    raise ValueError(f"unknown quant_coarse_topk impl {impl!r}")


@functools.partial(jax.jit, static_argnames=("bm", "bp", "impl"))
def assign(
    x: jnp.ndarray, pivots: jnp.ndarray,
    *, bm: int = 256, bp: int = 512, impl: str = "auto",
):
    """Nearest-pivot id + distance per row."""
    impl = ("pallas" if use_pallas() else "ref") if impl == "auto" else impl
    if impl == "ref":
        return ref.assign_ref(x, pivots)
    return assign_pallas(x, pivots, bm=bm, bp=bp,
                         interpret=impl == "interpret")


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "bq", "bk", "impl"))
def flash_attention(
    q, k, v, *, causal: bool = True, window: int | None = None,
    scale: float | None = None, bq: int = 128, bk: int = 128,
    impl: str = "auto",
):
    """Attention over (b, n, h, d) tensors; GQA via kv-head broadcast."""
    impl = ("pallas" if use_pallas() else "ref") if impl == "auto" else impl
    if impl == "ref":
        return ref.flash_attention_ref(
            q, k, v, causal=causal, window=window, scale=scale)
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, scale=scale,
        bq=bq, bk=bk, interpret=impl == "interpret")
