"""Pallas kernel sweeps: interpret-mode kernels vs pure-jnp oracles across
shapes, dtypes, block sizes, and accumulator widths — including the padded
``kernels.ops`` wrappers on real-workload odd shapes (10-class heads,
3-channel inputs, odd batches) that violate the raw kernels' block
divisibility asserts."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.int_matmul import int_matmul
from repro.kernels.multithreshold import infer_out_dtype, multithreshold
from repro.kernels.quantize import quantize


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 128, 256),
                                   (128, 512, 128), (384, 256, 128)])
def test_int_matmul_raw(m, k, n):
    rng = np.random.default_rng(m + k + n)
    x = rng.integers(-128, 128, size=(m, k)).astype(np.int8)
    w = rng.integers(-128, 128, size=(k, n)).astype(np.int8)
    got = int_matmul(jnp.asarray(x), jnp.asarray(w), interpret=True)
    want = ref.int_matmul_ref(jnp.asarray(x), jnp.asarray(w))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("bm,bk,bn", [(128, 128, 128), (256, 128, 128)])
def test_int_matmul_blocks(bm, bk, bn):
    rng = np.random.default_rng(0)
    x = rng.integers(-128, 128, size=(256, 256)).astype(np.int8)
    w = rng.integers(-128, 128, size=(256, 256)).astype(np.int8)
    got = int_matmul(jnp.asarray(x), jnp.asarray(w), bm=bm, bk=bk, bn=bn,
                     interpret=True)
    want = ref.int_matmul_ref(jnp.asarray(x), jnp.asarray(w))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_int_matmul_fused_dequant():
    rng = np.random.default_rng(1)
    x = rng.integers(-8, 8, size=(128, 128)).astype(np.int8)
    w = rng.integers(-8, 8, size=(128, 128)).astype(np.int8)
    s = rng.uniform(0.01, 0.1, size=(128,)).astype(np.float32)
    b = rng.normal(size=(128,)).astype(np.float32)
    got = int_matmul(jnp.asarray(x), jnp.asarray(w), jnp.asarray(s),
                     jnp.asarray(b), interpret=True)
    want = ref.int_matmul_ref(jnp.asarray(x), jnp.asarray(w),
                              jnp.asarray(s), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_int_matmul_sira_int16_accumulator():
    """SIRA bound <= 15 bits → the int32 accumulator is stored as int16,
    still exact."""
    rng = np.random.default_rng(2)
    # |acc| <= 128*3*3 = 1152 < 2^14
    x = rng.integers(-3, 4, size=(128, 128)).astype(np.int8)
    w = rng.integers(-3, 4, size=(128, 128)).astype(np.int8)
    got = int_matmul(jnp.asarray(x), jnp.asarray(w), acc_bits=12,
                     interpret=True)
    assert got.dtype == jnp.int16
    want = ref.int_matmul_ref(jnp.asarray(x), jnp.asarray(w), acc_bits=12)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n_thr,out_dtype", [(3, jnp.int8), (15, jnp.int8),
                                             (255, jnp.int32)])
def test_multithreshold_sweep(n_thr, out_dtype):
    rng = np.random.default_rng(n_thr)
    x = rng.integers(-1000, 1000, size=(256, 128)).astype(np.int32)
    thr = np.sort(rng.integers(-900, 900, size=(n_thr, 128)), axis=0
                  ).astype(np.int32)
    got = multithreshold(jnp.asarray(x), jnp.asarray(thr), out_bias=-2,
                         out_dtype=out_dtype, interpret=True)
    want = ref.multithreshold_ref(jnp.asarray(x), jnp.asarray(thr),
                                  out_bias=-2, out_dtype=out_dtype)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_multithreshold_matches_searchsorted():
    """The VPU compare-count form equals the paper's binary-search form."""
    rng = np.random.default_rng(9)
    x = rng.integers(-500, 500, size=(128, 128)).astype(np.int32)
    thr = np.sort(rng.integers(-400, 400, size=(7, 128)), axis=0
                  ).astype(np.int32)
    a = ref.multithreshold_ref(jnp.asarray(x), jnp.asarray(thr))
    b = ref.multithreshold_searchsorted_ref(jnp.asarray(x),
                                            jnp.asarray(thr))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("qmin,qmax,dtype", [(-128, 127, jnp.int8),
                                             (-8, 7, jnp.int8),
                                             (0, 15, jnp.int8)])
def test_quantize_sweep(qmin, qmax, dtype):
    rng = np.random.default_rng(qmax)
    x = rng.normal(size=(256, 128)).astype(np.float32) * 3
    s = rng.uniform(0.01, 0.3, size=(128,)).astype(np.float32)
    z = np.zeros((128,), np.float32)
    got = quantize(jnp.asarray(x), jnp.asarray(s), jnp.asarray(z),
                   qmin=qmin, qmax=qmax, out_dtype=dtype, interpret=True)
    want = ref.quantize_ref(jnp.asarray(x), jnp.asarray(s), jnp.asarray(z),
                            qmin=qmin, qmax=qmax, out_dtype=dtype)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kernel_pipeline_matches_streamlined_graph():
    """int_matmul + multithreshold == the SIRA-streamlined graph tail."""
    from repro.core import (Graph, ScaledIntRange, SiraModel, Streamline,
                            convert_tails_to_thresholds)
    rng = np.random.default_rng(3)
    K, M = 128, 128
    g = Graph(inputs=["X"], outputs=[])
    s_in = g.add_initializer(0.05, "s_in")
    zp = g.add_initializer(0.0)
    b8 = g.add_initializer(8.0)
    g.add_node("Quant", ["X", s_in, zp, b8], ["Xq"], dict(signed=1))
    W = rng.normal(size=(K, M))
    w = g.add_initializer(W, "W")
    sw = g.add_initializer(np.abs(W).max(axis=0) / 7, "sw")
    zw = g.add_initializer(0.0)
    b4 = g.add_initializer(4.0)
    g.add_node("Quant", [w, sw, zw, b4], ["Wq"], dict(signed=1))
    g.add_node("MatMul", ["Xq", "Wq"], ["mm"])
    g.add_node("Relu", ["mm"], ["act"])
    sa = g.add_initializer(0.5)
    za = g.add_initializer(0.0)
    ba = g.add_initializer(4.0)
    g.add_node("Quant", ["act", sa, za, ba], ["Y"], dict(signed=0))
    g.outputs = ["Y"]
    inp = {"X": ScaledIntRange(lo=np.asarray(-1.0), hi=np.asarray(1.0))}
    model, _ = Streamline().apply(SiraModel(g.copy(), inp))
    res = model.metadata["aggregation"]
    g2, specs = convert_tails_to_thresholds(res.graph, inp)
    assert len(specs) == 1

    x = rng.uniform(-1, 1, size=(128, K))
    want = g.execute({"X": x})["Y"]

    # kernel pipeline: quantize → int matmul → multithreshold → rescale
    xq = np.clip(np.round(x / 0.05), -128, 127).astype(np.int8)
    wq = np.clip(np.round(W / (np.abs(W).max(axis=0) / 7)), -8, 7
                 ).astype(np.int8)
    acc = int_matmul(jnp.asarray(xq), jnp.asarray(wq), interpret=True)
    thr = specs[0].thresholds.T.astype(np.int32)      # (N, C)
    cnt = multithreshold(jnp.asarray(np.asarray(acc, np.int32)),
                         jnp.asarray(thr),
                         out_bias=int(specs[0].out_bias),
                         out_dtype=jnp.int32, interpret=True)
    got = np.asarray(cnt, np.float64) * 0.5           # final Mul(qs_Y)
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-9)


# --------------------------------------------------------------------------
# padded wrappers: odd (non-block-divisible) shapes through the Pallas path
# --------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(1, 49, 10), (7, 3, 5), (130, 200, 10),
                                   (8, 64, 100)])
def test_int_matmul_odd_shapes_padded(m, k, n):
    rng = np.random.default_rng(m * k + n)
    x = rng.integers(-128, 128, size=(m, k)).astype(np.int8)
    w = rng.integers(-8, 8, size=(k, n)).astype(np.int8)
    got = ops.int_matmul(jnp.asarray(x), jnp.asarray(w),
                         use_pallas=True, interpret=True)
    want = ref.int_matmul_ref(jnp.asarray(x), jnp.asarray(w))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_int_matmul_odd_shapes_fused_dequant():
    rng = np.random.default_rng(5)
    x = rng.integers(-8, 8, size=(6, 49)).astype(np.int8)
    w = rng.integers(-8, 8, size=(49, 10)).astype(np.int8)
    s = rng.uniform(0.01, 0.1, size=(10,)).astype(np.float32)
    b = rng.normal(size=(10,)).astype(np.float32)
    got = ops.int_matmul(jnp.asarray(x), jnp.asarray(w), jnp.asarray(s),
                         jnp.asarray(b), use_pallas=True, interpret=True)
    want = ref.int_matmul_ref(jnp.asarray(x), jnp.asarray(w),
                              jnp.asarray(s), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_int_matmul_scalar_scale_broadcasts_to_all_columns():
    """Per-tensor (size-1) scale must apply to every output column — the
    padded wrapper used to pad a scalar with ones, scaling only col 0."""
    x = jnp.ones((4, 8), jnp.int8)
    w = jnp.ones((8, 10), jnp.int8)
    s = jnp.asarray([0.5], jnp.float32)
    got = np.asarray(ops.int_matmul(x, w, s, use_pallas=True,
                                    interpret=True))
    np.testing.assert_array_equal(got, np.full((4, 10), 4.0, np.float32))


@pytest.mark.parametrize("m,c,n_thr", [(5, 3, 3), (1, 10, 15), (33, 130, 7)])
def test_multithreshold_odd_shapes_padded(m, c, n_thr):
    rng = np.random.default_rng(m + c + n_thr)
    x = rng.integers(-500, 500, size=(m, c)).astype(np.int32)
    thr = np.sort(rng.integers(-400, 400, size=(n_thr, c)), axis=0
                  ).astype(np.int32)
    got = ops.multithreshold(jnp.asarray(x), jnp.asarray(thr), out_bias=-1,
                             out_dtype=jnp.int32, use_pallas=True,
                             interpret=True)
    want = ref.multithreshold_ref(jnp.asarray(x), jnp.asarray(thr),
                                  out_bias=-1, out_dtype=jnp.int32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m,c", [(3, 10), (1, 1), (257, 5)])
def test_quantize_odd_shapes_padded(m, c):
    rng = np.random.default_rng(m + c)
    x = rng.normal(size=(m, c)).astype(np.float32) * 3
    s = rng.uniform(0.01, 0.3, size=(c,)).astype(np.float32)
    z = np.zeros((c,), np.float32)
    got = ops.quantize(jnp.asarray(x), jnp.asarray(s), jnp.asarray(z),
                       use_pallas=True, interpret=True)
    want = ref.quantize_ref(jnp.asarray(x), jnp.asarray(s), jnp.asarray(z))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------------------------------------------------
# out_dtype overflow regression: the old int8 default wrapped 8-bit
# unsigned tails (count 255 → -1)
# --------------------------------------------------------------------------

def test_infer_out_dtype():
    assert infer_out_dtype(3, -2) == jnp.int8
    assert infer_out_dtype(255, -128) == jnp.int8    # signed 8-bit fits
    assert infer_out_dtype(255, 0) == jnp.int16      # unsigned 8-bit: 255
    assert infer_out_dtype(2 ** 16, 0) == jnp.int32


def test_multithreshold_default_dtype_no_overflow():
    """8-bit unsigned tail: count reaches 255 and must not wrap negative
    under the default output dtype (interpret mode)."""
    x = jnp.full((8, 4), 10_000, jnp.int32)
    thr = jnp.asarray(np.tile(np.arange(255, dtype=np.int32)[:, None],
                              (1, 4)))
    for out in (multithreshold(x, thr, interpret=True),
                ref.multithreshold_ref(x, thr)):
        arr = np.asarray(out)
        assert arr.min() >= 0, "8-bit unsigned tail wrapped negative"
        assert int(arr.max()) == 255


@pytest.mark.parametrize("B,Sq,H,KV,hd,cap", [(2, 128, 4, 2, 64, 0.0),
                                              (1, 256, 8, 8, 64, 50.0),
                                              (2, 128, 6, 2, 32, 0.0)])
def test_flash_attention_kernel(B, Sq, H, KV, hd, cap):
    from repro.kernels.flash_attention import (flash_attention_fwd,
                                               flash_attention_ref)
    rng = np.random.default_rng(Sq + H)
    q = jnp.asarray(rng.normal(size=(B, Sq, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Sq, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Sq, KV, hd)), jnp.float32)
    got = flash_attention_fwd(q, k, v, bq=64, bk=64, logit_cap=cap,
                              interpret=True)
    want = flash_attention_ref(q, k, v, logit_cap=cap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_matches_model_attention():
    """The Pallas kernel agrees with the model's jnp chunked attention."""
    from repro.kernels.flash_attention import flash_attention_fwd
    from repro.models.attention import flash_attention as model_fa
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(2, 128, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 128, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 128, 2, 32)), jnp.float32)
    a = flash_attention_fwd(q, k, v, bq=64, bk=64, interpret=True)
    b = model_fa(q, k, v, chunk=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)
