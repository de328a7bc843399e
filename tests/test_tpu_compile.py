"""Every Pallas kernel on the serving path compiles for a TPU v5e chip.

The chip is described, not attached: ``jax.experimental.topologies``
gives the devices of a ``v5e:2x2`` host and the TPU compiler lowers each
kernel for one of them, through Mosaic — what the interpreter used by the
other kernel tests cannot check (block tiling, SMEM/VMEM access rules,
dynamic indexing). Shapes are those of one khi-serve shard (N = 2^20,
d = 768, m = 4, bucket B = 32) in the two forms the engine calls:
batched, and one query at a time under ``vmap``. The topology is
described inside a fixture, never at import, so that only the worker
that runs these tests loads the TPU library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import gather_l2_filter as GF
from repro.kernels import scan_topk as ST
from repro.kernels.gather_l2 import gather_l2_blocked_raw

N, D, M, B, C = 1 << 20, 768, 4, 32, 128
W, W_CAP = 8, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _vmapped(raw, n_shared):
    """The engine's per-lane form: ``raw`` called with one query at a time
    (``x[None]`` ... ``[0]``) under ``vmap``. The first ``n_shared``
    arguments after the ids are the shared corpus planes."""
    def fn(ids, *args):
        shared, lanes = args[:n_shared], args[n_shared:]

        def one(i, *lane):
            return raw(i[None], *shared, *[x[None] for x in lane])[0]
        return jax.vmap(one)(ids, *lanes)
    return fn


F32, I8, I32 = jnp.float32, jnp.int8, jnp.int32
# name -> (function, argument shapes)
CASES = {
    "gather_l2_filter": (
        GF.gather_l2_filter_blocked_raw,
        [((B, C), I32), ((N, D), F32), ((N, M), F32), ((B, D), F32),
         ((B, M), F32), ((B, M), F32)]),
    "gather_l2_filter_vmap": (
        _vmapped(GF.gather_l2_filter_blocked_raw, 2),
        [((B, C), I32), ((N, D), F32), ((N, M), F32), ((B, D), F32),
         ((B, M), F32), ((B, M), F32)]),
    "gather_l2_filter_q8": (
        GF.gather_l2_filter_q8_blocked_raw,
        [((B, C), I32), ((N, D), I8), ((N, 1), F32), ((N, M), F32),
         ((B, D), F32), ((B, M), F32), ((B, M), F32)]),
    "gather_l2_filter_q8_vmap": (
        _vmapped(GF.gather_l2_filter_q8_blocked_raw, 3),
        [((B, C), I32), ((N, D), I8), ((N, 1), F32), ((N, M), F32),
         ((B, D), F32), ((B, M), F32), ((B, M), F32)]),
    "gather_l2_blocked": (
        gather_l2_blocked_raw,
        [((B, C), I32), ((N, D), F32), ((B, D), F32)]),
    "scan_topk_k10": (
        lambda *a: ST.scan_topk_raw(*a, k=10),
        [((N, D), F32), ((N, M), F32), ((B, D), F32), ((B, M), F32),
         ((B, M), F32)]),
    "scan_topk_k100": (
        lambda *a: ST.scan_topk_raw(*a, k=100),
        [((N, D), F32), ((N, M), F32), ((B, D), F32), ((B, M), F32),
         ((B, M), F32)]),
    "scan_topk_q8": (
        lambda *a: ST.scan_topk_q8_raw(*a, k=40),
        [((N, D), I8), ((N, 1), F32), ((N, M), F32), ((B, D), F32),
         ((B, M), F32), ((B, M), F32)]),
    "scan_topk_mask": (
        lambda *a: ST.scan_topk_mask_raw(*a, k=10),
        [((N, D), F32), ((N,), F32), ((B, D), F32)]),
    "scan_topk_windows": (
        lambda *a: ST.scan_topk_windows_raw(*a, k=10, w_cap=W_CAP),
        [((N, D), F32), ((N, M), F32), ((B, D), F32), ((B, M), F32),
         ((B, M), F32), ((B, W), I32), ((B, W), I32)]),
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    # lowered through Mosaic, not the interpreter
    assert "tpu_custom_call" in compiled.as_text()
