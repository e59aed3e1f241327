"""Needle codec + fid + TTL + superblock round-trip tests."""

import struct

import numpy as np
import pytest

from seaweedfs_tpu.storage import types as t
from seaweedfs_tpu.storage.needle import (
    Needle, NeedleError, masked_crc, padding_length, actual_size,
    VERSION2, VERSION3, FLAG_HAS_NAME, FLAG_IS_COMPRESSED,
)
from seaweedfs_tpu.storage.superblock import SuperBlock, ReplicaPlacement, TTL


def test_fid_roundtrip():
    fid = t.FileId(volume_id=3, key=0x01637037, cookie=0xD6000000)
    s = str(fid)
    assert s.startswith("3,")
    back = t.FileId.parse(s)
    assert back == fid


def test_fid_parse_with_delta():
    f = t.FileId.parse("7,2b0fca9077_3")
    assert f.volume_id == 7
    assert f.key == 0x2B + 3
    assert f.cookie == 0x0FCA9077


def test_fid_rejects_garbage():
    for bad in ("nocomma", "1,ff", "x,0102030405"):
        with pytest.raises(ValueError):
            t.FileId.parse(bad)


def test_needle_roundtrip_simple():
    n = Needle(id=0x1234, cookie=0xABCD0123, data=b"hello world")
    blob = n.to_bytes()
    assert len(blob) % 8 == 0
    m = Needle.from_bytes(blob)
    assert m.id == n.id and m.cookie == n.cookie and m.data == n.data
    assert m.checksum == masked_crc(b"hello world")
    assert m.append_at_ns == n.append_at_ns


def test_needle_roundtrip_all_fields():
    n = Needle(id=9, cookie=1, data=b"x" * 100, name=b"file.txt",
               mime=b"text/plain", pairs=b'{"k":"v"}',
               last_modified=1700000000, ttl=TTL.parse("3h"))
    blob = n.to_bytes()
    m = Needle.from_bytes(blob)
    assert m.name == b"file.txt"
    assert m.mime == b"text/plain"
    assert m.pairs == b'{"k":"v"}'
    assert m.last_modified == 1700000000
    assert m.ttl == TTL.parse("3h")


def test_needle_version2_no_timestamp():
    n = Needle(id=5, cookie=2, data=b"abc")
    b3 = n.to_bytes(VERSION3)
    n2 = Needle(id=5, cookie=2, data=b"abc")
    b2 = n2.to_bytes(VERSION2)
    assert len(b2) < len(b3)
    m = Needle.from_bytes(b2, VERSION2)
    assert m.data == b"abc"


def test_needle_crc_detection():
    n = Needle(id=1, cookie=1, data=b"payload")
    blob = bytearray(n.to_bytes())
    blob[t.NEEDLE_HEADER_SIZE + 4 + 2] ^= 0x40  # flip a data bit
    with pytest.raises(NeedleError):
        Needle.from_bytes(bytes(blob))


def test_padding_formula_matches_reference():
    # reference: pad = 8 - ((16 + size + 4 + 8) % 8): in 1..8, so the
    # record length is a strict multiple of 8 and never unpadded
    for size in range(0, 64):
        p = padding_length(size, VERSION3)
        assert 1 <= p <= 8
        assert (t.NEEDLE_HEADER_SIZE + size + 4 + 8 + p) % 8 == 0
        assert actual_size(size, VERSION3) % 8 == 0


def test_needle_empty_data_is_delete_marker():
    n = Needle(id=7, cookie=3, data=b"")
    blob = n.to_bytes()
    m = Needle.from_bytes(blob)
    assert m.size == 0 and m.data == b""


def test_masked_crc_known_vector():
    # crc32c("123456789") = 0xE3069283; mask = rot17 + 0xa282ead8
    c = 0xE3069283
    expected = (((c >> 15) | (c << 17)) + 0xA282EAD8) & 0xFFFFFFFF
    assert masked_crc(b"123456789") == expected


@pytest.mark.parametrize("length", [0, 1, 7, 8, 9, (4 << 20) + 3])
@pytest.mark.parametrize("kind", ["bytearray", "bytearray-odd-start",
                                  "numpy-slice", "readonly-view"])
def test_crc32c_over_a_buffer_equals_crc32c_over_its_bytes(length, kind):
    import numpy as np
    from seaweedfs_tpu.native import rs_native
    raw = np.random.default_rng(length).integers(
        0, 256, length + 16, dtype=np.uint8)
    view = {"bytearray": lambda: memoryview(bytearray(raw))[:length],
            "bytearray-odd-start":
                lambda: memoryview(bytearray(raw))[3:3 + length],
            "numpy-slice": lambda: raw[5:5 + length],
            "readonly-view": lambda: memoryview(raw.tobytes())[1:1 + length],
            }[kind]()
    want = rs_native.crc32c(bytes(view))
    assert rs_native.crc32c(view) == want
    assert rs_native.crc32c(view, 0x1234) == \
        rs_native.crc32c(bytes(view), 0x1234)
    assert masked_crc(view) == masked_crc(bytes(view))


def _bitwise_crc32c(data: bytes, crc: int = 0) -> int:
    crc ^= 0xFFFFFFFF
    for b in data:
        crc ^= b
        for _ in range(8):
            crc = (crc >> 1) ^ (0x82F63B78 if crc & 1 else 0)
    return crc ^ 0xFFFFFFFF


@pytest.mark.parametrize("length", [3 * 8192 - 1, 3 * 8192, 3 * 8192 + 9,
                                    2 * 3 * 8192 + 8 * 100 + 5])
def test_crc32c_is_the_bitwise_definition_across_its_strides(length):
    """The native CRC takes three blocks of 8 KiB side by side and joins
    them: over lengths around its stride, from 0 and chained, it is the
    bit-at-a-time definition."""
    from seaweedfs_tpu.native import rs_native
    data = np.random.default_rng(length).integers(
        0, 256, length, dtype=np.uint8).tobytes()
    assert rs_native.crc32c(data) == _bitwise_crc32c(data)
    assert rs_native.crc32c(data, 0xDEADBEEF) == \
        _bitwise_crc32c(data, 0xDEADBEEF)
    head = rs_native.crc32c(data[:5000])
    assert rs_native.crc32c(data[5000:], head) == rs_native.crc32c(data)


def test_crc32c_makes_no_copy_of_a_writable_buffer():
    import tracemalloc
    from seaweedfs_tpu.native import rs_native
    buf = bytearray(b"\x07" * ((4 << 20) + 3))
    view = memoryview(buf)[1:]
    want = rs_native.crc32c(bytes(view))
    tracemalloc.start()
    try:
        assert rs_native.crc32c(view) == want
        _, peak = tracemalloc.get_traced_memory()
        assert peak < 64 << 10, f"{peak} bytes allocated for a view"
        # a read-only view still goes through bytes(): the copy the
        # writable branch saves
        readonly = memoryview(bytes(buf))[1:]
        tracemalloc.reset_peak()
        held, _ = tracemalloc.get_traced_memory()
        assert rs_native.crc32c(readonly) == want
        _, peak = tracemalloc.get_traced_memory()
        assert peak - held > 4 << 20
    finally:
        tracemalloc.stop()


def test_ttl_parse_and_bytes():
    for s, minutes in [("3m", 3), ("4h", 240), ("5d", 7200),
                       ("1w", 10080), ("", 0)]:
        ttl = TTL.parse(s)
        assert ttl.minutes == minutes
        assert TTL.from_bytes(ttl.to_bytes()) == ttl
        assert str(ttl) == s


def test_ttl_rejects_bad():
    with pytest.raises(ValueError):
        TTL.parse("3x")
    with pytest.raises(ValueError):
        TTL.parse("300m")


def test_replica_placement():
    rp = ReplicaPlacement.parse("012")
    assert rp.diff_dc == 0 and rp.diff_rack == 1 and rp.same_rack == 2
    assert rp.copy_count == 4
    assert ReplicaPlacement.from_byte(rp.to_byte()) == rp
    with pytest.raises(ValueError):
        ReplicaPlacement.parse("9")


def test_superblock_roundtrip():
    sb = SuperBlock(version=3, replica_placement=ReplicaPlacement.parse("001"),
                    ttl=TTL.parse("7d"), compaction_revision=5)
    b = sb.to_bytes()
    assert len(b) == 8
    back = SuperBlock.from_bytes(b)
    assert back == sb
