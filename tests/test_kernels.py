"""Pallas kernel validation (interpret mode on CPU) against pure oracles.

Per the deliverable: sweep shapes/dtypes/code distributions and
assert_allclose (bit-equality for decode; fp tolerance for the fused GEMM)
against the ref.py oracles.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import stats, tpu_format
from repro.kernels import ops, ref
from repro.kernels.fused_decode_matmul import encode_tiled, matmul_pallas


@pytest.mark.parametrize("n_elem", [128 * 32, 128 * 32 * 3 + 5, 100_000])
@pytest.mark.parametrize("alpha", [1.2, 1.9])
@pytest.mark.parametrize("spl", [32, 64])
def test_decode_kernel_matches_oracle(n_elem, alpha, spl):
    bits = stats.synthesize_fp8_weights((n_elem,), alpha=alpha,
                                        seed=n_elem % 97)
    c = tpu_format.encode(bits, sym_per_lane=spl)
    got = ops.decode_tpu_format(c)
    np.testing.assert_array_equal(got, bits.reshape(-1))


def test_decode_kernel_degenerate_codebooks():
    # single-symbol codebook (1-bit codes) and near-uniform (4-bit codes)
    for bits in [np.full(128 * 64, 0b0_0111_010, np.uint8),
                 (np.arange(128 * 64) * 11 % 256).astype(np.uint8)]:
        c = tpu_format.encode(bits, sym_per_lane=32)
        np.testing.assert_array_equal(ops.decode_tpu_format(c), bits)


@pytest.mark.parametrize("M,K,N", [(8, 64, 128), (16, 128, 256)])
@pytest.mark.parametrize("alpha", [1.9])
def test_fused_decode_matmul_matches_ref(M, K, N, alpha):
    S = 32
    w_bits = stats.synthesize_fp8_weights((K, N), alpha=alpha, seed=K + N)
    tiled = encode_tiled(w_bits, sym_per_lane=S)
    x = np.random.default_rng(0).normal(size=(M, K)).astype(np.float32) * 0.1
    got = matmul_pallas(jnp.asarray(x), tiled, interpret=True)
    want = ref.fused_decode_matmul_ref(x, w_bits)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-2, atol=5e-2)


def test_fused_decode_matmul_bitexact_weight_path():
    """The decoded weight inside the fused kernel is bit-exact: compare a
    matmul against an identity input which reads the weight out directly."""
    K, N, S = 64, 128, 32
    w_bits = stats.synthesize_fp8_weights((K, N), alpha=1.9, seed=5)
    tiled = encode_tiled(w_bits, sym_per_lane=S)
    eye = np.eye(K, dtype=np.float32)
    got = np.asarray(matmul_pallas(jnp.asarray(eye), tiled, interpret=True))
    want = np.asarray(
        jnp.asarray(w_bits).view(jnp.float8_e4m3fn).astype(jnp.bfloat16)
        .astype(jnp.float32))
    np.testing.assert_array_equal(got, want)


def test_kernel_entry_points_pick_interpret_from_backend():
    """Every Pallas entry point defaults to ``interpret=None``, resolved
    by the one backend helper (compiled on a TPU, interpreted elsewhere);
    no signature in src/ hard-codes an interpreter default."""
    import inspect
    import pathlib
    import jax
    from repro.kernels import (ecf8_decode, flash_fwd, fused_decode_matmul,
                               interpret_mode)
    from repro.kvcache import kernels as kv_kernels
    entry_points = [
        kv_kernels.decode_pages, kv_kernels.decode_page_indices_pallas,
        kv_kernels.decode_pages_sharded, ecf8_decode.decode_pallas,
        fused_decode_matmul.matmul_pallas, flash_fwd.flash_fwd_pallas,
        ops.decode_tpu_format, ops.fused_decode_matmul]
    for fn in entry_points:
        param = inspect.signature(fn).parameters["interpret"]
        assert param.default is None, fn
    assert interpret_mode() == (jax.default_backend() != "tpu")
    src = pathlib.Path(ops.__file__).resolve().parents[1]
    for path in src.rglob("*.py"):
        assert "interpret: bool = True" not in path.read_text(), path


def test_in_graph_decoders_dispatch_on_backend(monkeypatch):
    """The serving step's in-graph decoders follow the backend helper: on
    a TPU the weight decode (``store.materialize``) and the cold-page
    decode (``paged.page_gather``) trace the Pallas kernels, elsewhere
    their jnp references."""
    import jax
    import repro.kernels as K
    from repro.core.store import compress_array, materialize
    from repro.kvcache import codec, paged
    bits = stats.synthesize_fp8_weights((64, 256), alpha=1.5, seed=3)
    ct = compress_array(bits)
    n_kv, ps, hd, n = 2, 4, 16, 3
    _, L, _ = codec.plane_spec("bfloat16")
    e = n_kv * ps * hd
    sds = jax.ShapeDtypeStruct
    pool = sds((5, n_kv, ps, hd), jnp.bfloat16)
    cpool = (sds((n, 8, codec.LANES), jnp.uint8),
             sds((n, codec.sm_bytes("bfloat16", e)), jnp.uint8),
             sds((n, 3, L), jnp.int32), sds((n, 256), jnp.int32))
    table = sds((2, 3), jnp.int32)
    for on_tpu in (False, True):
        monkeypatch.setattr(K, "interpret_mode", lambda: not on_tpu)
        monkeypatch.setattr(paged, "interpret_mode", lambda: not on_tpu)
        # fresh callables: a traced function's cache does not see the patch
        weight = str(jax.make_jaxpr(lambda c: materialize(c))(ct))
        pages = str(jax.make_jaxpr(
            lambda *a: paged.page_gather(*a))(pool, table, cpool))
        assert ("pallas_call" in weight) == on_tpu
        assert ("pallas_call" in pages) == on_tpu


def test_flash_fwd_kernel_matches_reference():
    """flash_fwd (interpret on CPU) == dense causal GQA attention."""
    import jax
    from repro.kernels.flash_fwd import flash_fwd_pallas
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 4, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    got = flash_fwd_pallas(q, k, v, bq=128, bk=128)
    kk, vv = jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kk) * 64 ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((256, 256), bool)), s, -1e30)
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
