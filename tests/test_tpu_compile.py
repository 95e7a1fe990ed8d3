"""The Pallas kernels compile for a TPU v5e at qwen3-8b widths.

Each test lowers one kernel (``interpret=False``) for a *described* v5e
chip — no chip attached — and compiles it with the TPU compiler, which
refuses illegal block shapes, scalar reads from vector memory and
over-budget VMEM that interpret mode accepts.  Shapes are the serving
path's: KV pages of 8 kv heads x 16 tokens x 128 (bf16, 12-bit codes), a
4096 x 12288 weight, the fused decode-matmul at K=4096, N=12288, M <= 8.

The topology is described in a module-scoped fixture (it loads libtpu,
which only one process may hold), never while modules are imported.
"""
import os

import pytest
import jax
import jax.numpy as jnp

from repro.core.tpu_format import DEFAULT_SYM_PER_LANE, LANES

S = DEFAULT_SYM_PER_LANE


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory placed on one described v5e chip."""
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one)


def _compiles_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# the qwen3-8b.decode cell's cold pool: 2048 slots of 8 x 16 x 128 bf16,
# a 128-byte stride budget, 12-bit codes over 256 exponent symbols
_POOL = 2048
_PAGE_ELEMS = 8 * 16 * 128                      # kv heads x page x head_dim


def _cold_pool(sds):
    return (sds((_POOL, 128, LANES), jnp.uint8),
            sds((_POOL, _PAGE_ELEMS), jnp.uint8),
            sds((_POOL, 3, 12), jnp.int32), sds((_POOL, 256), jnp.int32))


def test_page_decode_kernel_compiles(sds):
    """The whole pool in one call: its tables and packed permutations fit
    the 1 MiB of SMEM."""
    from repro.kvcache import kernels
    pay, _, tab, perm = _cold_pool(sds)
    _compiles_kernel(
        lambda p, t, pr: kernels.decode_page_indices_pallas(
            p, t, pr, n_elem=_PAGE_ELEMS, interpret=False),
        pay, tab, perm)


def test_page_decode_applies_the_permutation_without_a_gather(sds):
    """The compiled cold-pool decode maps canonical indices to symbols in
    the kernel: no XLA gather is left, which on a TPU runs element by
    element.  The control, the permutation as the jnp decode's gather
    (``codec.finish_pages_jnp``), compiles to one."""
    import re
    from repro.kvcache import codec, kernels
    gather = re.compile(r"\bgather\(")
    pool = _cold_pool(sds)
    compiled = _compiles_kernel(
        lambda *a: kernels.decode_pages(
            *a, n_elem=_PAGE_ELEMS, dtype_name="bfloat16", interpret=False),
        *pool)
    assert not gather.search(compiled.as_text())
    control = jax.jit(lambda i, sm, pr: codec.finish_pages_jnp(
        i, sm, pr, n_elem=_PAGE_ELEMS, dtype_name="bfloat16")).lower(
            sds((_POOL, _PAGE_ELEMS // LANES, LANES), jnp.int32), pool[1],
            pool[3]).compile()
    assert gather.search(control.as_text())


def test_weight_decode_kernel_compiles(sds):
    from repro.kernels import ecf8_decode
    C = 4096 * 12288 // (S * LANES)
    tab = [sds((8,), jnp.int32)] * 3 + [sds((16,), jnp.int32)]
    _compiles_kernel(
        lambda p, sm, *t: ecf8_decode.decode_pallas(
            p, sm, *t, sym_per_lane=S, interpret=False),
        sds((C, 96, LANES), jnp.uint8), sds((C, S // 2, LANES), jnp.uint8),
        *tab)


@pytest.mark.parametrize("m", [1, 8])
def test_fused_decode_matmul_compiles(sds, m):
    from repro.kernels import fused_decode_matmul as fused
    K, N = 4096, 12288
    tab = [sds((8,), jnp.int32)] * 3 + [sds((16,), jnp.int32)]
    _compiles_kernel(
        lambda x, p, sm, *t: fused._matmul_impl(
            x, p, sm, *t, sym_per_lane=S, k=K, n=N, interpret=False,
            out_dtype=jnp.bfloat16),
        sds((m, K), jnp.bfloat16),
        sds((K // S, N // LANES, 96, LANES), jnp.uint8),
        sds((K // S, N // LANES, S // 2, LANES), jnp.uint8), *tab)


def test_flash_fwd_kernel_compiles(sds):
    from repro.kernels.flash_fwd import flash_fwd_pallas
    _compiles_kernel(
        lambda q, k, v: flash_fwd_pallas(q, k, v, interpret=False),
        sds((1, 32, 1024, 128), jnp.bfloat16),
        sds((1, 8, 1024, 128), jnp.bfloat16),
        sds((1, 8, 1024, 128), jnp.bfloat16))


def test_weight_decode_compiles_replicated_on_mesh(topo, monkeypatch):
    """The serving step's weight decode on a data mesh: a Mosaic kernel
    cannot be partitioned by XLA, so under ``weights_replicated_over``
    ``materialize`` runs it per chip (shard_map) — compiled for 2x2."""
    import numpy as np
    import repro.kernels as K
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core.store import (CompressedMeta, CompressedTensor,
                                  materialize, weights_replicated_over)
    monkeypatch.setattr(K, "interpret_mode", lambda: False)
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    rep = NamedSharding(mesh, P())
    n = 4096 * 1024
    C = n // (S * LANES)
    arrays = {"payload": (C, 96, LANES), "signmant": (C, S // 2, LANES)}
    arrays = {k: jax.ShapeDtypeStruct(v, jnp.uint8, sharding=rep)
              for k, v in arrays.items()}
    for k, size in (("lj_limit", 8), ("first_lj", 8), ("offset", 8),
                    ("perm", 16)):
        arrays[k] = jax.ShapeDtypeStruct((size,), jnp.int32, sharding=rep)
    meta = CompressedMeta(fmt="tpu", shape=(4096, 1024), n_elem=n,
                          sym_per_lane=S)
    x = jax.ShapeDtypeStruct((8, 4096), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("data")))

    def step(x, arrays):
        with weights_replicated_over(mesh):
            w = materialize(CompressedTensor(arrays=arrays, meta=meta),
                            dtype=jnp.bfloat16)
        return x @ w

    _compiles_kernel(step, x, arrays)
