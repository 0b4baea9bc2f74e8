"""RCB binary format: control really is data (roundtrip + integrity)."""
import json

import pytest
from hypothesis import given, settings, strategies as st

from repro.core.rcb import Op, RCB, RCBOp, RCBProgram, TensorDesc


def _prog(ops, tensors=None):
    tensors = tensors or {}
    return RCBProgram("t", tensors, [RCB(0, "layer", (), tuple(ops))])


def test_rcb_roundtrip_simple():
    ops = (RCBOp(Op.GEMM, ("y",), ("a", "b"), {"ta": False}),
           RCBOp(Op.FENCE),
           RCBOp(Op.HALT))
    blk = RCB(7, "layer", (3,), ops)
    blob = blk.encode()
    back, consumed = RCB.decode(memoryview(blob))
    assert consumed == len(blob)
    assert back == blk


def test_rcb_crc_detects_tamper():
    blk = RCB(1, "layer", (), (RCBOp(Op.RELU, ("y",), ("x",)),))
    blob = bytearray(blk.encode())
    blob[25] ^= 0xFF
    with pytest.raises(ValueError, match="CRC"):
        RCB.decode(memoryview(bytes(blob)))


def test_program_roundtrip_and_validate():
    tensors = {
        "x": TensorDesc("x", (4, 4), "float32", "input", ("batch", None)),
        "w": TensorDesc("w", (4, 4), "float32", "weight"),
        "y": TensorDesc("y", (4, 4), "float32", "output"),
    }
    prog = RCBProgram("mm", tensors,
                      [RCB(0, "layer", (),
                           (RCBOp(Op.GEMM, ("y",), ("x", "w")),))])
    prog.validate()
    back = RCBProgram.decode(prog.encode())
    assert back.name == "mm"
    assert back.tensors["x"].axes == ("batch", None)
    assert back.blocks[0].ops[0].op == Op.GEMM


def test_validate_catches_unbound_symbol():
    prog = _prog([RCBOp(Op.RELU, ("y",), ("nope",))])
    with pytest.raises(ValueError, match="unbound"):
        prog.validate()


def test_validate_catches_missing_dep():
    prog = RCBProgram("t", {}, [RCB(0, "layer", (99,), (RCBOp(Op.FENCE),))])
    with pytest.raises(ValueError, match="missing dep"):
        prog.validate()


# ---------------------------------------------------------------------------
# Binary v2: interned symtab + packed records; v1 kept for backward compat
# ---------------------------------------------------------------------------

def _rich_program():
    tensors = {
        "x": TensorDesc("x", (4, 4), "float32", "input", ("batch", None)),
        "w": TensorDesc("w", (4, 4), "float32", "weight"),
        "t": TensorDesc("t", (4, 4), "float32", "scratch"),
        "y": TensorDesc("y", (4, 4), "float32", "output"),
    }
    ops0 = (RCBOp(Op.GEMM, ("t",), ("x", "w"),
                  {"ta": False, "acc": "f32", "f": 1.5, "n": -7,
                   "l": [1, 2, 3], "nested": {"k": None, "b": True}}),
            RCBOp(Op.RELU, ("y",), ("t",)),
            RCBOp(Op.FENCE))
    return RCBProgram("rich", tensors,
                      [RCB(0, "layer", (), ops0),
                       RCB(1, "control", (0,), (RCBOp(Op.HALT),))])


def test_v2_roundtrip_equals_v1():
    """Cross-version decode: the same program through either wire format
    yields identical in-memory structures."""
    prog = _rich_program()
    blob_v1 = prog.encode(version=1)
    blob_v2 = prog.encode()                   # v2 is the default
    assert blob_v1 != blob_v2
    p1, p2 = RCBProgram.decode(blob_v1), RCBProgram.decode(blob_v2)
    assert p1.name == p2.name == "rich"
    assert p1.tensors == p2.tensors
    assert p1.blocks == p2.blocks
    assert p2.tensors["x"].axes == ("batch", None)
    assert p2.blocks[0].ops[0].attrs["nested"] == {"k": None, "b": True}


def test_v2_smaller_than_v1():
    prog = _rich_program()
    assert len(prog.encode()) < len(prog.encode(version=1))


def test_v1_decode_backward_compat():
    """A v1 blob (old provisioning payloads) still decodes."""
    prog = _rich_program()
    back = RCBProgram.decode(prog.encode(version=1))
    assert back.blocks[0].ops[0].op == Op.GEMM
    back.validate()


def test_v2_crc_rejects_corrupt_symtab():
    """Integrity first: a flipped byte inside the v2 symbol table fails the
    whole-program CRC before anything is parsed."""
    blob = bytearray(_rich_program().encode())
    # symtab starts right after the 20-byte header + name; corrupt inside it
    sym_off = 20 + len("rich") + 6
    blob[sym_off] ^= 0xFF
    with pytest.raises(ValueError, match="CRC"):
        RCBProgram.decode(bytes(blob))


def test_v2_crc_rejects_corrupt_op_payload():
    blob = bytearray(_rich_program().encode())
    blob[-20] ^= 0xFF                       # inside the last block
    with pytest.raises(ValueError, match="CRC"):
        RCBProgram.decode(bytes(blob))


def test_v2_unknown_version_rejected():
    blob = bytearray(_rich_program().encode())
    blob[4] = 99                            # version field (little-endian)
    import struct as _struct
    import zlib as _zlib
    body = bytes(blob[:-4])
    blob[-4:] = _struct.pack("<I", _zlib.crc32(body) & 0xFFFFFFFF)
    with pytest.raises(ValueError, match="version"):
        RCBProgram.decode(bytes(blob))


_sym = st.text(alphabet="abcdefgh_0123456789", min_size=1, max_size=8)
_attr_val = st.one_of(st.integers(-1000, 1000), st.booleans(),
                      st.floats(-1e3, 1e3, allow_nan=False),
                      st.lists(st.integers(0, 64), max_size=4))


@given(st.lists(
    st.builds(RCBOp,
              st.sampled_from(list(Op)),
              st.lists(_sym, max_size=3).map(tuple),
              st.lists(_sym, max_size=3).map(tuple),
              st.dictionaries(_sym, _attr_val, max_size=4)),
    max_size=16))
@settings(max_examples=50, deadline=None)
def test_property_block_roundtrip(ops):
    blk = RCB(3, "pipeline", (0, 1), tuple(ops))
    back, _ = RCB.decode(memoryview(blk.encode()))
    assert back == blk
