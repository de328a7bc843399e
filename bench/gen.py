"""Corpus and query pool of a benchmark run, made from ``--seed``.

The benchmark's own copy of the paper's Sec. 5.1 generator, kept here so
that a change to the program cannot move the yardstick:

* the corpus is a Gaussian mixture whose within-cluster spread lies in a
  random ``latent_dim``-dimensional subspace, with skewed attributes tied
  to the cluster by ``attr_corr``; the attributes are the deployment's
  (``attr_seed``), the embeddings the run's (``--seed``);
* a query is a corpus row plus isotropic noise, with a quantile-window
  box over ``cardinality`` (default: all m) attributes whose joint
  selectivity aims at a target drawn for it uniformly over the band
  ``[sigma * (1 - tol), sigma * (1 + tol)]``;
* the boxes are the traffic's (``box_seed``), the same list for every
  run; the run's seed draws the query rows and the noise, and the order
  of the boxes inside each batch. Every seed then serves the same work:
  the same boxes in the same batches, so the same lanes past the scan
  rule, and the spread of a cell's runs is the system's, not the draw's.

The window's search for that selectivity runs for all queries at once on
the device (a geometric bisection on the window width over all its
steps, counted on a fixed sample of the deployment's rows, one jitted
call per block of queries), where the program's generator makes up to 64
host passes over the whole corpus per query. Each box found is then
counted exactly over the corpus; candidates whose box misses the band
are dropped, in draw order, so every query of the pool is in the band.
"""

from __future__ import annotations

import numpy as np

BISECT_STEPS = 40
QUERY_BLOCK = 256
SAMPLE_ROWS = 32768     # rows the bisection counts on; the final box is
                        # counted on the whole corpus


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per stream; any non-negative seed (also past
    32 bits) is accepted."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def _attr(kind: str, z: np.ndarray, corr: float,
          rng: np.random.Generator) -> np.ndarray:
    eps = rng.standard_normal(z.shape[0])
    lat = corr * z + np.sqrt(max(1.0 - corr * corr, 0.0)) * eps
    if kind == "lognormal":
        return np.exp(1.5 * lat + 6.0)
    if kind == "year":
        u = 1.0 / (1.0 + np.exp(-lat))
        return (2005 + np.floor(20 * u ** 0.5)).clip(2005, 2024)
    if kind == "zipf":
        u = 1.0 / (1.0 + np.exp(-lat))
        return np.floor(1.0 / (u * 0.999 + 1e-3))
    if kind == "uniform":
        return 0.5 * (lat / 3.0 + 1.0).clip(0.0, 2.0)
    raise ValueError(f"unknown attribute kind {kind!r}")


def make_corpus(cfg: dict, seed: int):
    """(vecs (n, d) float32, attrs (n, m) float32) on the host.

    The attribute table and the rows' cluster membership come from the
    configuration's ``attr_seed``; the embeddings (cluster centres, the
    latent subspace, every row's spread) from ``seed``. The attribute
    tree, and with it every shape the serving programs are compiled for,
    is then the deployment's and the same for every seed, while the
    vectors and the queries differ from seed to seed."""
    n, d, m = cfg["n"], cfg["d"], cfg["m"]
    rng_a = rng_for(cfg["attr_seed"], 0)
    assign = rng_a.integers(0, cfg["n_clusters"], size=n)
    z = rng_a.standard_normal(cfg["n_clusters"])[assign]
    kinds = cfg["attr_kinds"]
    attrs = np.stack([_attr(kinds[j], z, cfg["attr_corr"], rng_a)
                      for j in range(m)], axis=1).astype(np.float32)
    rng = rng_for(seed, 0)
    centers = rng.standard_normal((cfg["n_clusters"], d)).astype(np.float32)
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    r = cfg["latent_dim"]
    basis = np.linalg.qr(rng.standard_normal((d, r)))[0].T
    basis = (basis * np.sqrt(d / r)).astype(np.float32)          # (r, d)
    vecs = rng.standard_normal((n, r), dtype=np.float32) @ basis
    vecs *= np.float32(cfg["cluster_std"])
    vecs += centers[assign]
    return vecs, attrs


def _bisect_fn():
    import jax
    import jax.numpy as jnp

    def inbox_share(attrs_t, lo, hi):
        inbox = jnp.ones((lo.shape[0], attrs_t.shape[1]), bool)
        for j in range(attrs_t.shape[0]):
            a = attrs_t[j][None, :]
            inbox &= (a >= lo[:, j:j + 1]) & (a <= hi[:, j:j + 1])
        return jnp.sum(inbox, axis=1, dtype=jnp.int32) / attrs_t.shape[1]

    @jax.jit
    def bisect(sorted_cols, sample_t, attrs_t, centers, use, w0, target):
        """Per query, the quantile-window width whose joint selectivity
        on the row sample lies nearest its target (in log), over every
        step of a geometric bisection; then that box's boundaries and its
        exact selectivity over the whole corpus. sorted_cols (n, m),
        sample_t (m, S), attrs_t (m, n), centers/use (Q, m), target (Q,)."""
        n = sorted_cols.shape[0]
        cols = jnp.arange(sorted_cols.shape[1])[None, :]

        def bounds(mult):
            width = jnp.minimum(w0 * mult, 1.0)[:, None]
            lo_q = jnp.clip(centers - width / 2, 0.0, 1.0)
            hi_q = jnp.clip(centers + width / 2, 0.0, 1.0)
            lo = sorted_cols[(lo_q * (n - 1)).astype(jnp.int32), cols]
            hi = sorted_cols[(hi_q * (n - 1)).astype(jnp.int32), cols]
            return (jnp.where(use, lo, -jnp.inf),
                    jnp.where(use, hi, jnp.inf))

        def step(_, st):
            lo_m, hi_m, best, best_err = st
            mult = jnp.sqrt(lo_m * hi_m)
            sel = inbox_share(sample_t, *bounds(mult))
            err = jnp.abs(jnp.log(jnp.maximum(sel, 1e-9) / target))
            better = err < best_err
            best = jnp.where(better, mult, best)
            best_err = jnp.where(better, err, best_err)
            lo_m = jnp.where(sel < target, mult, lo_m)
            hi_m = jnp.where(sel >= target, mult, hi_m)
            return lo_m, hi_m, best, best_err

        Q = centers.shape[0]
        st = (jnp.full((Q,), 0.1), jnp.full((Q,), 8.0), jnp.ones((Q,)),
              jnp.full((Q,), jnp.inf))
        _, _, best, _ = jax.lax.fori_loop(0, BISECT_STEPS, step, st)
        lo, hi = bounds(best)
        return lo, hi, inbox_share(attrs_t, lo, hi)

    return bisect


def make_pool(vecs: np.ndarray, attrs: np.ndarray, traffic: dict,
              n_queries: int, seed: int, query_noise: float):
    """``n_queries`` fresh queries: (Q (N, d), lo (N, m), hi (N, m),
    selectivity (N,)), each box in the traffic's selectivity band. The
    boxes come from the traffic's ``box_seed`` and are shuffled inside
    each batch by ``seed``; the query rows and the noise come from
    ``seed``."""
    import jax
    import jax.numpy as jnp

    n, m = attrs.shape
    sigma, tol = float(traffic["sigma"]), float(traffic["tol"])
    card = traffic.get("cardinality") or m
    lo_ok, hi_ok = sigma * (1 - tol), sigma * (1 + tol)
    w0 = sigma ** (1.0 / card)
    rng = rng_for(traffic["box_seed"], 1)
    attrs_d = jnp.asarray(attrs)
    sorted_cols = jnp.sort(attrs_d, axis=0)
    attrs_t = attrs_d.T
    # the deployment's rows are drawn independently, so its first rows
    # are a sample of it
    sample_t = attrs_t[:, :SAMPLE_ROWS]
    bisect = _bisect_fn()

    out_lo, out_hi, out_sel = [], [], []
    have = 0
    for _round in range(8):
        want = max(QUERY_BLOCK, int(1.25 * (n_queries - have)) + 64)
        want = -(-want // QUERY_BLOCK) * QUERY_BLOCK
        use = np.zeros((want, m), bool)
        for i in range(want):
            use[i, rng.permutation(m)[:card]] = True
        centers = rng.uniform(w0 / 2, 1 - w0 / 2, size=(want, m))
        target = rng.uniform(lo_ok, hi_ok, size=want)
        for s in range(0, want, QUERY_BLOCK):
            sl = slice(s, s + QUERY_BLOCK)
            lo, hi, sel = jax.device_get(bisect(
                sorted_cols, sample_t, attrs_t,
                jnp.asarray(centers[sl], jnp.float32), jnp.asarray(use[sl]),
                jnp.float32(w0), jnp.asarray(target[sl], jnp.float32)))
            ok = (sel >= lo_ok) & (sel <= hi_ok)
            out_lo.append(np.asarray(lo)[ok])
            out_hi.append(np.asarray(hi)[ok])
            out_sel.append(np.asarray(sel)[ok])
            have += int(ok.sum())
        if have >= n_queries:
            break
    if have < n_queries:
        raise RuntimeError(f"only {have} of {n_queries} boxes landed in the "
                           f"selectivity band [{lo_ok}, {hi_ok}]")
    rng = rng_for(seed, 1)
    B = int(traffic["batch"])
    order = np.arange(n_queries)
    for s in range(0, n_queries, B):
        order[s:s + B] = s + rng.permutation(min(B, n_queries - s))
    lo = np.concatenate(out_lo)[order].astype(np.float32)
    hi = np.concatenate(out_hi)[order].astype(np.float32)
    sel = np.concatenate(out_sel)[order]
    base = rng.integers(0, n, size=n_queries)
    noise = rng.standard_normal((n_queries, vecs.shape[1]), dtype=np.float32)
    Q = vecs[base] + np.float32(query_noise) * noise
    return Q, lo, hi, sel
