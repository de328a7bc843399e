"""Jit'd public wrappers: padding, dtype handling, interpret dispatch.

On this CPU container the kernels execute through ``interpret=True`` (the
kernel body runs step-by-step in Python/XLA-CPU); on a real TPU the same
calls lower to Mosaic. ``interpret=None`` auto-selects by backend.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import gather_l2 as _gather
from . import gather_l2_filter as _gather_filter
from . import l2dist as _l2
from . import ref as _ref
from . import scan_topk as _scan

__all__ = ["l2dist", "gather_l2", "gather_l2_filtered", "scan_topk",
           "gather_l2_filtered_q8", "scan_topk_q8", "scan_topk_windows",
           "use_pallas_default"]


def use_pallas_default() -> bool:
    return jax.default_backend() == "tpu"


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("interpret", "tb", "tn", "td"))
def _l2dist_qn(q, c, interpret: bool, tb: int, tn: int, td: int):
    B, N = q.shape[0], c.shape[0]
    qp = _pad_to(_pad_to(q, 0, tb), 1, td)
    cp = _pad_to(_pad_to(c, 0, tn), 1, td)
    out = _l2.l2dist_qn_raw(qp, cp, tb=tb, tn=tn, td=td, interpret=interpret)
    return out[:B, :N]


@functools.partial(jax.jit, static_argnames=("interpret", "tb", "tc", "td"))
def _l2dist_qc(q, c, interpret: bool, tb: int, tc: int, td: int):
    B, C = q.shape[0], c.shape[1]
    qp = _pad_to(_pad_to(q, 0, tb), 1, td)
    cp = _pad_to(_pad_to(_pad_to(c, 0, tb), 1, tc), 2, td)
    out = _l2.l2dist_qc_raw(qp, cp, tb=tb, tc=tc, td=td, interpret=interpret)
    return out[:B, :C]


def l2dist(q: jax.Array, c: jax.Array, *, interpret: Optional[bool] = None,
           tb: int = 8, tn: int = 128, td: int = 128) -> jax.Array:
    """Squared L2 distances.

    q (B, d) with c (N, d)    -> (B, N)   [all-pairs]
    q (B, d) with c (B, C, d) -> (B, C)   [per-query candidates]
    """
    interp = _auto_interpret(interpret)
    if c.ndim == 2:
        return _l2dist_qn(q, c, interp, tb, tn, td)
    if c.ndim == 3:
        return _l2dist_qc(q, c, interp, tb, tn, td)
    raise ValueError(f"bad candidate rank {c.ndim}")


@functools.partial(jax.jit, static_argnames=("interpret", "c_blk"))
def _gather_l2(idx, corpus, q, interpret: bool, c_blk: Optional[int]):
    if c_blk is None:
        return _gather.gather_l2_raw(idx, corpus, q, interpret=interpret)
    return _gather.gather_l2_blocked_raw(idx, corpus, q, c_blk=c_blk,
                                         interpret=interpret)


def gather_l2(idx: jax.Array, corpus: jax.Array, q: jax.Array,
              *, interpret: Optional[bool] = None,
              c_blk: Optional[int] = None) -> jax.Array:
    """Fused gather+distance: idx (B, C) into corpus (N, d), q (B, d) ->
    (B, C). Indices must be in-range (clamp upstream). ``c_blk`` selects
    the blocked kernel (C_BLK rows per grid step — the serving engine's
    form); ``None`` keeps the row-per-step validation form. Both are
    bitwise-equal (DESIGN.md §8)."""
    return _gather_l2(idx, corpus, q, _auto_interpret(interpret), c_blk)


@functools.partial(jax.jit, static_argnames=("interpret", "c_blk"))
def _gather_l2_filtered(idx, corpus, attrs, q, qlo, qhi, interpret: bool,
                        c_blk: int):
    return _gather_filter.gather_l2_filter_blocked_raw(
        idx, corpus, attrs, q, qlo, qhi, c_blk=c_blk, interpret=interpret)


def gather_l2_filtered(idx: jax.Array, corpus: jax.Array, attrs: jax.Array,
                       q: jax.Array, qlo: jax.Array, qhi: jax.Array,
                       *, interpret: Optional[bool] = None,
                       c_blk: int = 128) -> jax.Array:
    """Predicate-fused gather+distance: idx (B, C) int32 (-1 = pad/invalid)
    into corpus (N, d) / attrs (N, m), q (B, d), qlo/qhi (B, m) ->
    (B, C) f32 with +inf on invalid or out-of-range lanes. Finite lanes are
    bitwise-equal to ``gather_l2`` on the same ids (DESIGN.md §9); the
    oracle is ``gather_l2_filter_ref``."""
    return _gather_l2_filtered(idx, corpus, attrs, q, qlo, qhi,
                               _auto_interpret(interpret), c_blk)


@functools.partial(jax.jit, static_argnames=("k", "interpret", "n_blk"))
def _scan_topk(corpus, attrs, q, qlo, qhi, k: int, interpret: bool,
               n_blk: int):
    return _scan.scan_topk_raw(corpus, attrs, q, qlo, qhi, k=k, n_blk=n_blk,
                               interpret=interpret)


def scan_topk(corpus: jax.Array, attrs: jax.Array, q: jax.Array,
              qlo: jax.Array, qhi: jax.Array, *, k: int,
              interpret: Optional[bool] = None, n_blk: int = 512):
    """Predicate-fused brute-scan top-k: corpus (N, d) / attrs (N, m)
    against q (B, d) with boxes qlo/qhi (B, m) -> (ids (B, k) int32,
    dists (B, k) f32), exact masked top-k ascending, (-1, +inf) past the
    in-range count. Ids are bit-identical to the jnp oracle
    ``scan_topk_ref`` (dists up to f32 reduce order — DESIGN.md §10);
    this is the planner's ``strategy="scan"`` execution path."""
    return _scan_topk(corpus, attrs, q, qlo, qhi, k,
                      _auto_interpret(interpret), n_blk)


@functools.partial(jax.jit, static_argnames=("interpret", "c_blk"))
def _gather_l2_filtered_q8(idx, qcorpus, qscale, attrs, q, qlo, qhi,
                           interpret: bool, c_blk: int):
    return _gather_filter.gather_l2_filter_q8_blocked_raw(
        idx, qcorpus, qscale, attrs, q, qlo, qhi, c_blk=c_blk,
        interpret=interpret)


def gather_l2_filtered_q8(idx: jax.Array, qcorpus: jax.Array,
                          qscale: jax.Array, attrs: jax.Array, q: jax.Array,
                          qlo: jax.Array, qhi: jax.Array,
                          *, interpret: Optional[bool] = None,
                          c_blk: int = 128) -> jax.Array:
    """int8-replica form of ``gather_l2_filtered`` (DESIGN.md §12):
    idx (B, C) into qcorpus (N, d) int8 + qscale (N, 1) f32, dequantized
    in-kernel. On TPU each candidate DMAs its whole 32-row int8 tile
    (32·d bytes, as many as an f32 candidate's 8-row tile), so the gather
    saves VMEM work, not HBM bytes. Oracle: ``gather_l2_filter_q8_ref``."""
    return _gather_l2_filtered_q8(idx, qcorpus, qscale, attrs, q, qlo, qhi,
                                  _auto_interpret(interpret), c_blk)


@functools.partial(jax.jit, static_argnames=("k", "interpret", "n_blk"))
def _scan_topk_q8(qcorpus, qscale, attrs, q, qlo, qhi, k: int,
                  interpret: bool, n_blk: int):
    return _scan.scan_topk_q8_raw(qcorpus, qscale, attrs, q, qlo, qhi, k=k,
                                  n_blk=n_blk, interpret=interpret)


def scan_topk_q8(qcorpus: jax.Array, qscale: jax.Array, attrs: jax.Array,
                 q: jax.Array, qlo: jax.Array, qhi: jax.Array, *, k: int,
                 interpret: Optional[bool] = None, n_blk: int = 512):
    """int8-replica form of ``scan_topk`` (DESIGN.md §12): the corpus
    streams as int8 tiles + (N_BLK, 1) scale planes and dequantizes
    in-kernel. Ids bit-identical to ``scan_topk_q8_ref``; the engine
    reranks the over-fetched candidates through the f32 path."""
    return _scan_topk_q8(qcorpus, qscale, attrs, q, qlo, qhi, k,
                         _auto_interpret(interpret), n_blk)


@functools.partial(jax.jit, static_argnames=("k", "w_cap", "interpret"))
def _scan_topk_windows(corpus, attrs, q, qlo, qhi, starts, counts, k: int,
                       w_cap: int, interpret: bool):
    return _scan.scan_topk_windows_raw(corpus, attrs, q, qlo, qhi, starts,
                                       counts, k=k, w_cap=w_cap,
                                       interpret=interpret)


def scan_topk_windows(corpus: jax.Array, attrs: jax.Array, q: jax.Array,
                      qlo: jax.Array, qhi: jax.Array, starts: jax.Array,
                      counts: jax.Array, *, k: int, w_cap: int,
                      interpret: Optional[bool] = None):
    """Windowed brute-scan top-k over a POSITION-ordered corpus
    (DESIGN.md §12): starts/counts (B, W) int32 give each query's
    antichain windows (start = -1 pads; counts <= w_cap; sorted
    ascending per lane for the tie-break contract) -> (positions (B, k)
    int32, dists (B, k) f32). The hybrid planner's per-node scan path;
    oracle ``scan_topk_windows_ref``."""
    return _scan_topk_windows(corpus, attrs, q, qlo, qhi, starts, counts,
                              k, w_cap, _auto_interpret(interpret))


# re-export oracles for convenience
l2dist_qn_ref = _ref.l2dist_qn_ref
l2dist_qc_ref = _ref.l2dist_qc_ref
gather_l2_ref = _ref.gather_l2_ref
gather_l2_filter_ref = _ref.gather_l2_filter_ref
gather_l2_filter_q8_ref = _ref.gather_l2_filter_q8_ref
scan_topk_ref = _ref.scan_topk_ref
scan_topk_q8_ref = _ref.scan_topk_q8_ref
scan_topk_windows_ref = _ref.scan_topk_windows_ref
