"""Plain reference and the checks that decide ``correct``.

The reference is an exact in-box top-k by squared L2, written without
anything of the program: a float32 pass on the device (full-precision
matmul) picks ``CANDIDATES`` in-box candidates per query, and float64 on
the host ranks them. The float32 error is some 1e-5 of a distance, far
under the gap between a query's 10th and 64th neighbour, so the float64
top-k of the candidates is the exact top-k.

``answer_lowp`` is the control: the same scan put in the program's place
in bfloat16, the precision below the configuration's float32, returning
ids and the distances it computed.

The checks copy the arithmetic of ``chip_smoke.py`` (in-box test, float64
recomputation of every returned distance, the exact comparison up to
distance ties) and add the completeness number. Every number has its
limit in ``LIMITS``; ``PERF.md`` gives the readings each limit was set
from.
"""

from __future__ import annotations

import numpy as np

CANDIDATES = 64
BLOCK = 128

# each compared number may not exceed its limit (PERF.md gives the
# readings each was set from)
LIMITS = {
    "box_violations": 0,     # over all answers of the window
    "short_answers": 0,      # over all answers of the window
    "dist_err": 3e-5,        # the largest over all answers
    "scan_inexact": 0,       # over all batches, where held
}

# an answer whose rank gap exceeds this is not exact: over the swap of two
# float32-tied distances (~4e-7), under the bfloat16 control's gaps
EXACT_TOL = 1e-5


def _scan_fn(lowp: bool):
    import jax
    import jax.numpy as jnp

    dt = jnp.bfloat16 if lowp else jnp.float32
    prec = jax.lax.Precision.DEFAULT if lowp else jax.lax.Precision.HIGHEST

    @jax.jit
    def scan(vecs, xn, attrs_t, q, lo, hi):
        qd = q.astype(dt)
        qn = jnp.sum(jnp.square(qd.astype(jnp.float32)), axis=1)
        dot = jnp.matmul(qd, vecs.T, precision=prec,
                         preferred_element_type=jnp.float32)
        d = xn[None, :] - 2.0 * dot + qn[:, None]
        inbox = jnp.ones(d.shape, bool)
        for j in range(attrs_t.shape[0]):
            a = attrs_t[j][None, :]
            inbox &= (a >= lo[:, j:j + 1]) & (a <= hi[:, j:j + 1])
        d = jnp.where(inbox, jnp.maximum(d, 0.0), jnp.inf)
        neg, ids = jax.lax.top_k(-d, CANDIDATES)
        return ids, -neg, jnp.sum(inbox, axis=1, dtype=jnp.int32)

    return scan, dt


def _device_scan(vecs, attrs, Q, lo, hi, lowp: bool):
    """Per query: CANDIDATES (ids, distances) by the device scan, and the
    number of in-box rows."""
    import jax
    import jax.numpy as jnp

    scan, dt = _scan_fn(lowp)
    v = jnp.asarray(vecs).astype(dt)
    xn = jnp.sum(jnp.square(v.astype(jnp.float32)), axis=1)
    at = jnp.asarray(attrs).T
    B = Q.shape[0]
    ids = np.empty((B, CANDIDATES), np.int64)
    dd = np.empty((B, CANDIDATES), np.float32)
    cnt = np.empty((B,), np.int64)
    for s in range(0, B, BLOCK):
        e = min(s + BLOCK, B)
        pad = BLOCK - (e - s)       # one block shape: one compile
        q = np.concatenate([Q[s:e], np.zeros((pad, Q.shape[1]), np.float32)])
        l = np.concatenate([lo[s:e], np.full((pad, lo.shape[1]), np.inf,
                                             np.float32)])
        h = np.concatenate([hi[s:e], np.full((pad, hi.shape[1]), -np.inf,
                                             np.float32)])
        i, d, c = jax.device_get(scan(v, xn, at, q, l, h))
        ids[s:e], dd[s:e], cnt[s:e] = i[:e - s], d[:e - s], c[:e - s]
    del v, xn, at
    return ids, dd, cnt


def d64(vecs, Q, ids) -> np.ndarray:
    """float64 squared L2 of ``ids`` (B, k) against their queries; inf
    where an id is negative."""
    x = vecs[np.maximum(ids, 0)].astype(np.float64)
    d = np.sum((x - Q[:, None, :].astype(np.float64)) ** 2, axis=-1)
    return np.where(ids >= 0, d, np.inf)


def exact(vecs, attrs, Q, lo, hi, k: int):
    """Exact in-box top-k: (ids (B, k) -1 padded, float64 distances (B, k)
    inf padded, in-box row count (B,))."""
    cid, cd, cnt = _device_scan(vecs, attrs, Q, lo, hi, lowp=False)
    cid = np.where(np.isfinite(cd), cid, -1)
    dist = d64(vecs, Q, cid)
    order = np.lexsort((cid, dist), axis=1)[:, :k]
    ids = np.take_along_axis(cid, order, axis=1)
    return ids, np.take_along_axis(dist, order, axis=1), cnt


def answer_lowp(vecs, attrs, Q, lo, hi, k: int):
    """The control: the reference's scan in bfloat16, in the program's
    place. Returns (ids (B, k), distances (B, k) as it computed them)."""
    cid, cd, _ = _device_scan(vecs, attrs, Q, lo, hi, lowp=True)
    ids = np.where(np.isfinite(cd[:, :k]), cid[:, :k], -1).astype(np.int32)
    return ids, np.where(ids >= 0, cd[:, :k], np.inf).astype(np.float32)


# ------------------------------------------------------------------ checks
def box_violations(ids, attrs, lo, hi) -> np.ndarray:
    """Per lane: returned ids outside the corpus or their box, plus
    repeated ids."""
    n = attrs.shape[0]
    valid = ids >= 0
    bad = valid & (ids >= n)
    a = attrs[np.clip(ids, 0, n - 1)]                     # (B, k, m)
    inside = np.all((a >= lo[:, None]) & (a <= hi[:, None]), axis=-1)
    bad |= valid & ~inside
    s = np.sort(np.where(valid, ids, -1), axis=1)
    dup = (s[:, 1:] == s[:, :-1]) & (s[:, 1:] >= 0)
    return bad.sum(axis=1) + dup.sum(axis=1)


def short_answers(ids, inbox_count, k: int) -> np.ndarray:
    """Per lane: 1 where the answer holds fewer ids than min(k, rows in
    the box)."""
    got = np.sum(ids >= 0, axis=1)
    return (got < np.minimum(k, inbox_count)).astype(np.int64)


def dist_err(ids, dists, vecs, Q) -> np.ndarray:
    """Per lane: the largest |returned - float64| / max(float64, 1) over
    its returned ids; inf for a returned id with no finite distance."""
    ref = d64(vecs, Q, ids)
    got = dists.astype(np.float64)
    with np.errstate(invalid="ignore"):
        err = np.abs(got - ref) / np.maximum(ref, 1.0)
    err = np.where(ids >= 0, np.where(np.isfinite(got), err, np.inf), 0.0)
    return err.max(axis=1)


def rank_gap(ids, vecs, Q, ref_d) -> np.ndarray:
    """Per lane: the largest gap, rank by rank, between the float64
    distances of the returned ids (sorted) and the exact top-k's, as a
    share of the exact distance. Zero for an exact answer, and for one
    that differs only among tied distances."""
    got = np.sort(d64(vecs, Q, ids), axis=1)
    both_inf = ~np.isfinite(got) & ~np.isfinite(ref_d)
    with np.errstate(invalid="ignore"):
        gap = np.abs(got - ref_d) / np.maximum(ref_d, 1.0)
    gap = np.where(both_inf, 0.0, np.nan_to_num(gap, nan=np.inf))
    return gap.max(axis=1)


def scan_inexact(gap, lanes, scanned) -> np.ndarray:
    """Per batch: the answers that are not exact beyond the lanes that the
    service did not scan. ``lanes`` and ``scanned`` are per batch, in the
    order of the answers. The planner's routing bound may send a lane of
    a small box to the graph path, whose answers are approximate by
    design; a scanned lane is answered exactly."""
    out, s = np.zeros(len(lanes), np.int64), 0
    for b, (n, sc) in enumerate(zip(lanes, scanned)):
        off = int(np.sum(gap[s:s + n] > EXACT_TOL))
        out[b] = max(0, off - (n - sc))
        s += n
    return out


def recall(ids, ref_ids) -> np.ndarray:
    """Per lane: |returned & exact top-k| / |exact top-k| (1 for an empty
    box answered empty)."""
    out = np.empty(ids.shape[0])
    for i, (g, t) in enumerate(zip(ids, ref_ids)):
        t = t[t >= 0]
        out[i] = (len(np.intersect1d(g[g >= 0], t)) / len(t)) if len(t) \
            else 1.0
    return out


def judge(ids, dists, vecs, attrs, Q, lo, hi, ref_ids, ref_d, inbox_count,
          k: int, *, exact: bool, batches=None):
    """Every compared number of one window's answers, each with its limit.
    ``exact``: the traffic's boxes lie far under the scan rule, and every
    answer of a lane that the service scanned is held to the exact in-box
    top-k; ``batches`` is [(lanes, lanes scanned)] per batch, in order.
    Returns (checks {name: {"value", "limit"}}, per-lane fail mask,
    per-lane recall, per-lane rank gap or None)."""
    per = {
        "box_violations": box_violations(ids, attrs, lo, hi),
        "short_answers": short_answers(ids, inbox_count, k),
        "dist_err": dist_err(ids, dists, vecs, Q),
    }
    rec = recall(ids, ref_ids)
    checks, lane_fail = {}, np.zeros(ids.shape[0], bool)
    for name, vals in per.items():
        value = vals.sum() if name in ("box_violations", "short_answers") \
            else vals.max()
        checks[name] = {"value": float(value), "limit": LIMITS[name]}
        lane_fail |= vals > LIMITS[name]
    gap = None
    if exact:
        gap = rank_gap(ids, vecs, Q, ref_d)
        lanes, scanned = zip(*batches)
        over = scan_inexact(gap, lanes, scanned)
        checks["scan_inexact"] = {"value": float(over.sum()),
                                  "limit": LIMITS["scan_inexact"]}
        bad = np.repeat(over > LIMITS["scan_inexact"], lanes)
        lane_fail |= bad & (gap > EXACT_TOL)
    return checks, lane_fail, rec, gap


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
