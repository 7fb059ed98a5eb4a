"""Compiled Pallas executor backend: equivalence against the numpy
interpreter (``Graph.execute``) on the four QNN workloads, flow/API wiring,
and lowering details (acc_bits selection, epilogue fusion, error paths).

The bit-exactness contract: every tensor the SIRA analysis proves
integer-valued (quantizer outputs, integer matmul/conv accumulators,
thresholds, residual adds) must match the interpreter exactly.  Float
epilogues are compared at 1e-12 in float64 mode — XLA's FMA contraction
and reduction order differ from numpy by ≤1 ulp (documented in lower.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DEFAULT_STEPS, LoweringError, SiraModel, build_flow,
                        lower)
from repro.core.workloads import WORKLOADS

WL_NAMES = list(WORKLOADS)


def _optimized(name):
    return build_flow(WORKLOADS[name]()).model


def _feeds(model, batch=2, seed=0):
    shape = (batch,) + tuple(model.metadata["input_shape"][1:])
    (inp,) = model.graph.inputs
    r = model.input_ranges[inp]
    rng = np.random.default_rng(seed)
    lo = np.broadcast_to(np.asarray(r.lo, np.float64), shape)
    hi = np.broadcast_to(np.asarray(r.hi, np.float64), shape)
    return {inp: rng.uniform(lo, hi, size=shape)}


@pytest.mark.parametrize("name", WL_NAMES)
def test_backend_bitexact_vs_interpreter(name):
    """Pallas kernels in interpret mode, float64: the integer core is
    bit-exact and the float outputs match to 1e-12."""
    with jax.enable_x64(True):
        model = _optimized(name)
        compiled = model.compile(use_pallas=True, interpret=True)
        assert compiled.int_tensors, "no integer tensors lowered"
        compiled = model.compile(use_pallas=True, interpret=True,
                                 extra_outputs=compiled.int_tensors)
        feeds = _feeds(model)
        want = model.execute(feeds, record_all=True)
        got = compiled(feeds)
        for t in compiled.int_tensors:
            np.testing.assert_array_equal(
                got[t].astype(np.float64), want[t],
                err_msg=f"integer tensor {t} not bit-exact")
        for o in model.graph.outputs:
            np.testing.assert_allclose(got[o], want[o],
                                       rtol=1e-12, atol=1e-12)
        # the integer layers actually went through the kernels
        kinds = compiled.kernel_calls
        assert kinds.get("int_matmul", 0) + kinds.get("int_conv", 0) >= 1
        assert kinds.get("multithreshold", 0) >= 1
        assert kinds.get("quantize", 0) >= 1


@pytest.mark.parametrize("name", WL_NAMES)
def test_backend_fast_path_matches(name):
    """Default CPU path (jnp reference kernels, float32, fused epilogues)
    agrees with the interpreter to f32 precision on batched inputs."""
    model = _optimized(name)
    compiled = model.compile()
    feeds = _feeds(model, batch=4)
    want = model.execute(feeds)
    got = compiled(feeds)
    for o in model.graph.outputs:
        scale = max(float(np.abs(want[o]).max()), 1.0)
        np.testing.assert_allclose(got[o].astype(np.float64), want[o],
                                   rtol=1e-5, atol=1e-5 * scale)


def test_sira_acc_bits_reach_the_kernel():
    """The lowering takes acc_bits from the SIRA accumulator bound — the
    low-bit workloads must prove a narrow (≤15-bit, stored as int16)
    accumulator for at least one integer matmul/conv."""
    model = _optimized("TFC-w2a2")
    compiled = model.compile()
    acc = [op.acc_bits for op in compiled.plan
           if op.kind.startswith(("int_", "fused:int_"))]
    assert acc and all(b is not None and b <= 31 for b in acc)
    assert min(acc) <= 15, f"expected a SIRA-narrowed accumulator, got {acc}"


def test_fused_epilogue_in_f32_mode():
    """float32 mode fuses MatMul→Mul→Add into the int_matmul epilogue;
    float64 (exact) mode keeps the elementwise nodes separate."""
    model = _optimized("TFC-w2a2")
    fused = model.compile(dtype=jnp.float32)
    assert any(op.kind.startswith("fused:") for op in fused.plan)
    unfused = model.compile(dtype=jnp.float32, fuse_epilogue=False)
    assert not any(op.kind.startswith("fused:") for op in unfused.plan)
    feeds = _feeds(model)
    for o in model.graph.outputs:
        np.testing.assert_allclose(fused(feeds)[o], unfused(feeds)[o],
                                   rtol=1e-5, atol=1e-5)


def test_biased_conv_not_epilogue_fused():
    """Conv-with-bias followed by Mul/Add must add the bias *before* the
    scale chain — fusing the epilogue onto the raw accumulator would
    reorder them, so the lowering must keep them separate."""
    from repro.core import Graph, ScaledIntRange
    rng = np.random.default_rng(0)
    g = Graph(inputs=["X"], outputs=["out"])
    one = g.add_initializer(1.0)
    zero = g.add_initializer(0.0)
    b8 = g.add_initializer(8.0)
    g.add_node("Quant", ["X", one, zero, b8], ["Xq"], dict(signed=1))
    w = g.add_initializer(rng.integers(-3, 4, size=(4, 3, 3, 3)).astype(
        np.float64), "W")
    cb = g.add_initializer(rng.integers(-5, 6, size=(4,)).astype(
        np.float64), "Cb")
    g.add_node("Conv", ["Xq", w, cb], ["conv"], dict(stride=1, pad=1))
    s = g.add_initializer(rng.uniform(0.1, 0.5, size=(4, 1, 1)), "S")
    a = g.add_initializer(rng.normal(size=(4, 1, 1)), "A")
    g.add_node("Mul", ["conv", s], ["scaled"])
    g.add_node("Add", ["scaled", a], ["out"])
    model = SiraModel(g, {"X": ScaledIntRange(lo=np.full((), -5.0),
                                              hi=np.full((), 5.0))},
                      metadata=dict(input_shape=(1, 3, 8, 8)))
    compiled = lower(model, dtype=jnp.float32)
    assert not any(op.kind.startswith("fused:") for op in compiled.plan)
    feeds = {"X": np.random.default_rng(1).uniform(-5, 5,
                                                   size=(2, 3, 8, 8))}
    got = compiled(feeds)["out"]
    want = model.execute(feeds)["out"]
    np.testing.assert_allclose(got.astype(np.float64), want,
                               rtol=1e-5, atol=1e-5)


def test_step_compile_flow_step():
    """``step_compile`` in a build flow stores the executable under
    metadata['compiled'] without modifying the graph."""
    wl = WORKLOADS["TFC-w2a2"]()
    res = build_flow(wl, steps=list(DEFAULT_STEPS) + ["step_compile"])
    compiled = res.model.metadata["compiled"]
    assert compiled is res.model.metadata["compiled"]
    step = res.steps[-1]
    assert step.name == "step_compile" and not step.modified
    feeds = _feeds(res.model)
    got = compiled(feeds)
    want = res.model.execute(feeds)
    for o in res.model.graph.outputs:
        np.testing.assert_allclose(got[o].astype(np.float64), want[o],
                                   rtol=1e-5, atol=1e-4)


def test_compiled_retraces_per_batch_shape():
    model = _optimized("TFC-w2a2")
    compiled = model.compile()
    for batch in (1, 3, 8):
        feeds = _feeds(model, batch=batch)
        out = compiled(feeds)[model.graph.outputs[0]]
        assert out.shape[0] == batch


def test_integer_gemm_gets_int_matmul_route():
    """Gemm with integer operands and an integral bias must still route
    its matmul part through int_matmul with a SIRA-derived acc_bits (the
    synthetic sub-tensor has no range of its own — it is derived by
    shifting the Gemm output range by the bias)."""
    from repro.core import Graph, ScaledIntRange
    rng = np.random.default_rng(0)
    g = Graph(inputs=["X"], outputs=["Y"])
    one = g.add_initializer(1.0)
    zero = g.add_initializer(0.0)
    b4 = g.add_initializer(4.0)
    g.add_node("Quant", ["X", one, zero, b4], ["Xq"], dict(signed=1))
    w = g.add_initializer(rng.integers(-3, 4, size=(16, 6)).astype(
        np.float64), "W")
    c = g.add_initializer(rng.integers(-9, 9, size=(6,)).astype(
        np.float64), "C")
    g.add_node("Gemm", ["Xq", w, c], ["Y"])
    model = SiraModel(g, {"X": ScaledIntRange(lo=np.full((), -7.0),
                                              hi=np.full((), 7.0))})
    compiled = lower(model, dtype=jnp.float32)
    mm = [op for op in compiled.plan if op.kind == "int_matmul"]
    assert mm and mm[0].acc_bits is not None and mm[0].acc_bits <= 15
    feeds = {"X": np.random.default_rng(1).uniform(-7, 7, size=(3, 16))}
    np.testing.assert_array_equal(compiled(feeds)["Y"].astype(np.float64),
                                  model.execute(feeds)["Y"])
    # the synthetic matmul sub-tensor must not leak into int_tensors —
    # requesting them as extra_outputs is the documented exactness flow
    compiled2 = lower(model, dtype=jnp.float32,
                      extra_outputs=compiled.int_tensors)
    got = compiled2(feeds)
    assert all(t in got for t in compiled.int_tensors)


@pytest.mark.parametrize("op", ["MatMul", "Conv"])
def test_wide_integer_operands_take_int32_dot(op):
    """Operands that do not fit int8 (a 10-bit input quantizer) never
    reach the int8 kernel: they take the exact int32 dot route, named in
    the plan, and stay bit-exact."""
    from repro.core import Graph, ScaledIntRange
    rng = np.random.default_rng(0)
    g = Graph(inputs=["X"], outputs=["Y"])
    one = g.add_initializer(1.0)
    zero = g.add_initializer(0.0)
    b10 = g.add_initializer(10.0)
    g.add_node("Quant", ["X", one, zero, b10], ["Xq"], dict(signed=1))
    if op == "MatMul":
        w = rng.integers(-3, 4, size=(16, 6)).astype(np.float64)
        shape, kind = (3, 16), "int_dot"
    else:
        w = rng.integers(-3, 4, size=(4, 2, 3, 3)).astype(np.float64)
        shape, kind = (3, 2, 6, 6), "int_conv_dot"
    g.add_node(op, ["Xq", g.add_initializer(w, "W")], ["Y"],
               dict(stride=1, pad=1) if op == "Conv" else {})
    model = SiraModel(g, {"X": ScaledIntRange(lo=np.full((), -500.0),
                                              hi=np.full((), 500.0))})
    compiled = lower(model, dtype=jnp.float32, use_pallas=True,
                     interpret=True)
    assert [o.kind for o in compiled.plan if o.op_type == op] == [kind]
    feeds = {"X": np.random.default_rng(1).uniform(-500, 500, size=shape)}
    np.testing.assert_array_equal(compiled(feeds)["Y"].astype(np.float64),
                                  model.execute(feeds)["Y"])


def test_extra_outputs_validated_at_lower_time():
    model = _optimized("TFC-w2a2")
    with pytest.raises(LoweringError, match="extra output"):
        model.compile(extra_outputs=["no_such_tensor"])


def test_unsupported_op_raises_lowering_error():
    from repro.core import Graph, ScaledIntRange
    g = Graph(inputs=["X"], outputs=["Y"])
    g.add_node("Gather", ["T", "X"], ["Y"])
    g.add_initializer(np.arange(8.0), "T")
    model = SiraModel(g, {"X": ScaledIntRange(lo=np.zeros(()),
                                              hi=np.ones(()))})
    with pytest.raises(LoweringError):
        lower(model)


# --------------------------------------------------------------------------
# per-channel threshold extraction (regression for the .reshape(-1)[0]
# collapse of per-channel quantizer parameters)
# --------------------------------------------------------------------------

def _per_channel_tail_graph(C=4):
    from repro.core import Graph
    g = Graph(inputs=["X"], outputs=[])
    one = g.add_initializer(1.0)
    zero = g.add_initializer(0.0)
    b8 = g.add_initializer(8.0)
    g.add_node("Quant", ["X", one, zero, b8], ["Xq"], dict(signed=1))
    a = g.add_initializer(np.linspace(0.25, 2.0, C))
    b = g.add_initializer(np.linspace(-3.0, 2.0, C))
    g.add_node("Mul", ["Xq", a], ["m"])
    g.add_node("Add", ["m", b], ["n"])
    g.add_node("Relu", ["n"], ["r"])
    sq = g.add_initializer(np.linspace(0.2, 2.5, C))   # per-channel scale
    zq = g.add_initializer(0.0)
    b3 = g.add_initializer(3.0)
    g.add_node("Quant", ["r", sq, zq, b3], ["Y"], dict(signed=0))
    g.outputs = ["Y"]
    return g


@pytest.mark.parametrize("method", ["edge", "bisect"])
def test_per_channel_quantizer_thresholds_exact(method):
    from repro.core import ScaledIntRange, convert_tails_to_thresholds
    C = 4
    g = _per_channel_tail_graph(C)
    inp = {"X": ScaledIntRange(lo=np.full(C, -20.0), hi=np.full(C, 20.0))}
    g2, specs = convert_tails_to_thresholds(g, inp, method=method)
    assert len(specs) == 1
    assert np.asarray(specs[0].out_scale).size == C, \
        "per-channel out_scale collapsed"
    xs = np.arange(-20, 21, dtype=np.float64)
    X = np.broadcast_to(xs[:, None], (xs.size, C))
    want = g.execute({"X": X})["Y"]
    got = g2.execute({"X": X})["Y"]
    np.testing.assert_array_equal(got, want)


def test_mismatched_quantizer_granularity_rejected():
    """A quantizer granularity matching neither per-tensor nor the tail's
    channel count must be rejected (tail left composite), not miscompiled."""
    from repro.core import (Graph, ScaledIntRange,
                            convert_tails_to_thresholds)
    g = Graph(inputs=["X"], outputs=[])
    one = g.add_initializer(1.0)
    zero = g.add_initializer(0.0)
    b8 = g.add_initializer(8.0)
    g.add_node("Quant", ["X", one, zero, b8], ["Xq"], dict(signed=1))
    a = g.add_initializer(np.linspace(0.5, 1.5, 4).reshape(2, 2))  # C=4
    g.add_node("Mul", ["Xq", a], ["m"])
    sq = g.add_initializer(np.array([0.5, 1.0]))       # granularity 2 ≠ 1, 4
    zq = g.add_initializer(0.0)
    b3 = g.add_initializer(3.0)
    g.add_node("Quant", ["m", sq, zq, b3], ["Y"], dict(signed=0))
    g.outputs = ["Y"]
    inp = {"X": ScaledIntRange(lo=np.full((2, 2), -8.0),
                               hi=np.full((2, 2), 8.0))}
    g2, specs = convert_tails_to_thresholds(g, inp)
    assert specs == []                        # rejected, not miscompiled
    assert any(n.op_type == "Quant" and n.outputs == ["Y"]
               for n in g2.nodes)             # tail left in place
