"""Property test: RANDOM RCB programs execute identically in eager and
fused modes — the strongest form of the paper's portability claim (the same
control stream drives both execution environments, for *any* program in the
op vocabulary, not just hand-picked ones)."""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import rbl, rimfs
from repro.core.executor import Executor
from repro.core.rcb import Op, RCB, RCBOp, RCBProgram, TensorDesc


def _build_random_program(draw_ops, rng):
    """A random straight-line tensor program over (4,6)-shaped f32 buffers.

    Each step applies a random unary/binary op to previously defined
    symbols; the final symbol becomes the output.
    """
    tensors = {
        "in0": TensorDesc("in0", (4, 6), "float32", "input"),
        "w0": TensorDesc("w0", (6, 6), "float32", "weight"),
    }
    syms = ["in0"]
    ops = []
    for i, choice in enumerate(draw_ops):
        src = syms[choice % len(syms)]
        dst = f"t{i}"
        kind = choice % 4
        if kind == 0:
            tensors[dst] = TensorDesc(dst, (4, 6), "float32", "scratch")
            ops.append(RCBOp(Op.RELU, (dst,), (src,)))
        elif kind == 1:
            tensors[dst] = TensorDesc(dst, (4, 6), "float32", "scratch")
            ops.append(RCBOp(Op.SOFTMAX, (dst,), (src,), {"axis": -1}))
        elif kind == 2:
            other = syms[(choice // 4) % len(syms)]
            tensors[dst] = TensorDesc(dst, (4, 6), "float32", "scratch")
            ops.append(RCBOp(Op.ADD, (dst,), (src, other)))
        else:
            tensors[dst] = TensorDesc(dst, (4, 6), "float32", "scratch")
            ops.append(RCBOp(Op.GEMM, (dst,), (src, "w0")))
        syms.append(dst)
    out = syms[-1]
    tensors[out] = TensorDesc(out, tensors[out].shape, "float32", "output")
    prog = RCBProgram("rand", tensors, [RCB(0, "layer", (), tuple(ops))])
    prog.validate()
    return prog


@given(st.lists(st.integers(0, 1000), min_size=1, max_size=12),
       st.integers(0, 10_000))
@settings(max_examples=40, deadline=None)
def test_random_programs_eager_equals_fused(draw_ops, seed):
    rng = np.random.RandomState(seed)
    prog = _build_random_program(draw_ops, rng)
    w = rng.randn(6, 6).astype(np.float32) * 0.5
    x = rng.randn(4, 6).astype(np.float32)
    fs = rimfs.mount(rimfs.pack({"w0": w}))
    ex = Executor()

    bound = rbl.bind(prog, rimfs=fs, inputs={"in0": x})
    out_name = next(n for n, t in prog.tensors.items() if t.kind == "output")
    eager = np.asarray(ex.run(bound)[out_name])

    bound2 = rbl.bind(prog, rimfs=fs)
    fused = ex.fuse(bound2)
    out = fused({"in0": x}, ex.weights_from(bound2))[out_name]
    np.testing.assert_allclose(eager, np.asarray(out), rtol=1e-5, atol=1e-5)

    # control-as-data: the binary roundtrip of the same random program
    # still validates and produces identical eager results
    prog2 = RCBProgram.decode(prog.encode())
    bound3 = rbl.bind(prog2, rimfs=fs, inputs={"in0": x})
    eager2 = np.asarray(ex.run(bound3)[out_name])
    np.testing.assert_array_equal(eager, eager2)
