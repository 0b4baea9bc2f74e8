"""RIMFS: zero-copy semantics, alignment, CRC integrity, image roundtrip,
device residency (pin-once, zero re-upload)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import rhal, rimfs


def test_pack_mount_roundtrip(rng):
    files = {
        "w1": rng.randn(16, 8).astype(np.float32),
        "w2": rng.randint(-128, 127, (3, 5, 7), dtype=np.int8),
        "scalar": np.asarray(3.5, np.float64),
    }
    img = rimfs.pack(files)
    fs = rimfs.mount(img)
    assert sorted(fs.files()) == sorted(files)
    for k, v in files.items():
        np.testing.assert_array_equal(fs.read(k), v)
    assert fs.verify() and fs.verify_image()


def test_zero_copy_view(rng):
    w = rng.randn(64, 64).astype(np.float32)
    img = rimfs.pack({"w": w})
    fs = rimfs.mount(img)
    view = fs.read("w")
    # a true view: no copy — base buffer is the image itself
    assert view.base is not None
    assert not view.flags["OWNDATA"]


def test_alignment(rng):
    files = {f"t{i}": rng.randn(i + 1).astype(np.float32) for i in range(7)}
    fs = rimfs.mount(rimfs.pack(files))
    for name in fs.files():
        off, _ = fs.address_of(name)
        assert off % rimfs.ALIGN == 0


def test_crc_detects_bit_flip(rng):
    img = bytearray(rimfs.pack({"w": rng.randn(32).astype(np.float32)}))
    fs0 = rimfs.mount(bytes(img))
    off, n = fs0.address_of("w")
    img[off + 5] ^= 0x10
    fs = rimfs.mount(bytes(img))
    with pytest.raises(rimfs.RIMFSError, match="CRC"):
        fs.verify()
    with pytest.raises(rimfs.RIMFSError, match="CRC"):
        fs.verify_image()


def test_mount_file_mmap(tmp_path, rng):
    w = rng.randn(128).astype(np.float32)
    rimfs.save_file(tmp_path / "img.rimfs", {"w": w})
    fs = rimfs.mount_file(tmp_path / "img.rimfs")
    np.testing.assert_array_equal(fs.read("w"), w)
    assert fs.verify()


def test_resident_views_alias_image_no_copy(rng):
    """The round-trip property: the host views the resident upload consumed
    ARE views of the mounted image bytes — no staging copy anywhere."""
    w = rng.randn(64, 64).astype(np.float32)
    img = rimfs.pack({"w": w})
    fs = rimfs.mount(img)
    drv = rhal.make_eager_driver()
    ri = fs.resident(drv)
    view = ri.host_view("w")
    assert np.shares_memory(view, np.frombuffer(img, np.uint8))
    np.testing.assert_array_equal(view, w)
    # the uploaded device buffer round-trips the same bits
    np.testing.assert_array_equal(np.asarray(ri["w"]), w)
    # and its offset comes from address_of's aligned placement
    off, nbytes = fs.address_of("w")
    assert off % rimfs.ALIGN == 0 and nbytes == w.nbytes


def test_resident_is_pinned_once_per_driver(rng):
    files = {f"w{i}": rng.randn(32, 32).astype(np.float32)
             for i in range(4)}
    fs = rimfs.mount(rimfs.pack(files))
    drv = rhal.make_eager_driver(debug_arena=True)
    ri1 = fs.resident(drv)
    moved = drv.stats.get("dma_bytes", 0)
    assert moved == sum(v.nbytes for v in files.values())
    pinned = drv.arena.bytes_in_use
    # second resident call: same object, zero additional DMA, zero arena
    ri2 = fs.resident(drv)
    assert ri2 is ri1
    assert drv.stats.get("dma_bytes", 0) == moved
    assert drv.arena.bytes_in_use == pinned
    # a different driver gets its own pinning
    drv2 = rhal.make_eager_driver()
    fs.resident(drv2)
    assert drv2.stats.get("dma_bytes", 0) == moved
    # unpin releases the arena ranges and invalidates the cache entry
    ri1.unpin()
    assert drv.arena.bytes_in_use == pinned - moved
    assert fs.resident(drv) is not ri1


def test_resident_pins_subset_and_extends(rng):
    """bind-style subset pinning: only requested files upload; later
    requests extend incrementally; already-pinned files never re-move."""
    files = {f"w{i}": rng.randn(16, 16).astype(np.float32)
             for i in range(3)}
    fs = rimfs.mount(rimfs.pack(files))
    drv = rhal.make_eager_driver()
    ri = fs.resident(drv, names=["w0"])
    assert ri.files() == ["w0"]
    assert drv.stats.get("dma_bytes", 0) == files["w0"].nbytes
    ri2 = fs.resident(drv, names=["w0", "w2"])       # extend
    assert ri2 is ri and sorted(ri.files()) == ["w0", "w2"]
    assert drv.stats["dma_bytes"] == files["w0"].nbytes \
        + files["w2"].nbytes


def test_resident_cache_drops_dead_drivers(rng):
    """The per-driver cache must not keep a collected driver's weight
    copy alive (elasticity churn creates many short-lived drivers)."""
    import gc
    fs = rimfs.mount(rimfs.pack({"w": rng.randn(8).astype(np.float32)}))
    drv = rhal.make_eager_driver()
    fs.resident(drv)
    assert len(fs._resident) == 1
    del drv
    gc.collect()
    drv2 = rhal.make_eager_driver()
    fs.resident(drv2)                     # prunes the dead entry
    assert len(fs._resident) == 1


def test_overhead_small(rng):
    """Paper Table 2: runtime memory dominated by weights, minimal overhead."""
    w = rng.randn(512, 512).astype(np.float32)     # 1 MB payload
    fs = rimfs.mount(rimfs.pack({"w": w}))
    assert fs.overhead_bytes() < 0.01 * fs.total_bytes()


@given(st.dictionaries(
    st.text("abcdef", min_size=1, max_size=6),
    st.tuples(st.sampled_from(["float32", "int8", "int32", "float16"]),
              st.lists(st.integers(1, 5), min_size=0, max_size=3)),
    min_size=1, max_size=8))
@settings(max_examples=30, deadline=None)
def test_property_roundtrip(spec):
    rng = np.random.RandomState(42)
    files = {k: (np.asarray(rng.randn(*shape)) * 10).astype(dt)
             for k, (dt, shape) in spec.items()}
    fs = rimfs.mount(rimfs.pack(files))
    assert fs.verify()
    for k, v in files.items():
        np.testing.assert_array_equal(fs.read(k), v)
