"""Protocol round-trip tests.

Mirrors the reference's fluvio-protocol unit tests: varint edge cases,
record/batch/recordset encode-decode round trips, compression variants,
raw (shallow) batch decode, and request framing.
"""

import numpy as np
import pytest

from fluvio_tpu.protocol.api import (
    ApiVersionKey,
    ApiVersionsRequest,
    ApiVersionsResponse,
    RequestMessage,
    decode_request_header,
)
from fluvio_tpu.protocol.codec import ByteReader, ByteWriter, DecodeError
from fluvio_tpu.protocol.compression import Compression
from fluvio_tpu.protocol.error import ApiError, ErrorCode
from fluvio_tpu.protocol.record import Batch, Record, RecordSet
from fluvio_tpu.protocol.varint import (
    varint_decode,
    varint_decode_array,
    varint_encode,
    varint_encode_array,
    varint_encoded_sizes,
    varint_size,
)


class TestVarint:
    @pytest.mark.parametrize(
        "value", [0, 1, -1, 63, 64, -64, -65, 127, 128, 300, -300, 2**31, -(2**31), 2**62, -(2**62)]
    )
    def test_roundtrip(self, value):
        buf = bytearray()
        varint_encode(buf, value)
        assert len(buf) == varint_size(value)
        decoded, pos = varint_decode(buf, 0)
        assert decoded == value
        assert pos == len(buf)

    def test_truncated(self):
        buf = bytearray()
        varint_encode(buf, 10**12)
        with pytest.raises(ValueError):
            varint_decode(buf[:-1], 0)

    def test_vectorized_roundtrip(self):
        rng = np.random.default_rng(0)
        values = np.concatenate(
            [
                rng.integers(-(2**31), 2**31, size=1000),
                np.array([0, 1, -1, 2**62, -(2**62), 127, -128]),
            ]
        ).astype(np.int64)
        sizes = varint_encoded_sizes(values)
        # scalar sizes agree
        for v, s in zip(values.tolist()[:50], sizes.tolist()[:50]):
            assert varint_size(v) == s
        starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
        out = np.zeros(int(sizes.sum()), dtype=np.uint8)
        ends = varint_encode_array(values, out, starts)
        assert (ends == starts + sizes).all()
        # scalar decode agrees
        for i in [0, 1, 5, 500, len(values) - 1]:
            v, pos = varint_decode(out, int(starts[i]))
            assert v == values[i]
            assert pos == ends[i]
        # vector decode agrees
        decoded, new_pos = varint_decode_array(out, starts)
        np.testing.assert_array_equal(decoded, values)
        np.testing.assert_array_equal(new_pos, ends)


class TestRecord:
    def roundtrip(self, rec: Record) -> Record:
        w = ByteWriter()
        rec.encode(w)
        return Record.decode(ByteReader(w.bytes()))

    def test_value_only(self):
        out = self.roundtrip(Record(value=b"hello fluvio"))
        assert out.value == b"hello fluvio"
        assert out.key is None

    def test_key_value(self):
        out = self.roundtrip(Record(value=b"v" * 1000, key=b"k1", offset_delta=7, timestamp_delta=-5))
        assert out.value == b"v" * 1000
        assert out.key == b"k1"
        assert out.offset_delta == 7
        assert out.timestamp_delta == -5

    def test_empty(self):
        out = self.roundtrip(Record())
        assert out.value == b""
        assert out.key is None


class TestBatch:
    def test_roundtrip(self):
        records = [Record(value=f"rec-{i}".encode(), key=b"k") for i in range(10)]
        batch = Batch.from_records(records, base_offset=100, first_timestamp=1234)
        w = ByteWriter()
        batch.encode(w)
        out = Batch.decode(ByteReader(w.bytes()))
        assert out.base_offset == 100
        assert out.header.last_offset_delta == 9
        assert out.header.first_timestamp == 1234
        assert out.computed_last_offset() == 110
        assert [r.value for r in out.records] == [f"rec-{i}".encode() for i in range(10)]
        assert [r.offset_delta for r in out.records] == list(range(10))

    @pytest.mark.parametrize(
        "codec",
        [Compression.NONE, Compression.GZIP, Compression.ZSTD,
         Compression.LZ4, Compression.SNAPPY],
    )
    def test_compression_roundtrip(self, codec):
        records = [Record(value=b"x" * 500) for _ in range(50)]
        batch = Batch.from_records(records, compression=codec)
        w = ByteWriter()
        batch.encode(w)
        out = Batch.decode(ByteReader(w.bytes()))
        assert out.header.compression() == codec
        assert len(out.records) == 50
        assert all(r.value == b"x" * 500 for r in out.records)
        if codec != Compression.NONE:
            raw = Batch.decode(ByteReader(w.bytes()), parse_records=False)
            assert raw.raw_record_count == 50
            assert len(raw.raw_records) < 50 * 500  # actually compressed

    def test_shallow_decode_then_materialize(self):
        records = [Record(value=f"{i}".encode()) for i in range(5)]
        batch = Batch.from_records(records, base_offset=3)
        w = ByteWriter()
        batch.encode(w)
        shallow = Batch.decode(ByteReader(w.bytes()), parse_records=False)
        assert shallow.records_len() == 5
        assert shallow.raw_records is not None
        mats = shallow.memory_records()
        assert [r.value for r in mats] == [b"0", b"1", b"2", b"3", b"4"]

    def test_corrupt_truncated(self):
        batch = Batch.from_records([Record(value=b"abc")])
        w = ByteWriter()
        batch.encode(w)
        with pytest.raises(DecodeError):
            Batch.decode(ByteReader(w.bytes()[: len(w.bytes()) - 3]))


class TestRecordSet:
    def test_multi_batch_roundtrip(self):
        rs = RecordSet()
        rs.add(Batch.from_records([Record(value=b"a"), Record(value=b"b")], base_offset=0))
        rs.add(Batch.from_records([Record(value=b"c")], base_offset=2))
        w = ByteWriter()
        rs.encode(w)
        out = RecordSet.decode(ByteReader(w.bytes()))
        assert len(out.batches) == 2
        assert out.total_records() == 3
        assert out.base_offset() == 0
        assert out.last_offset() == 3

    def test_empty(self):
        w = ByteWriter()
        RecordSet().encode(w)
        out = RecordSet.decode(ByteReader(w.bytes()))
        assert out.batches == []
        assert out.last_offset() is None


class TestApiFraming:
    def test_request_roundtrip(self):
        req = ApiVersionsRequest(client_version="9.9.9")
        msg = RequestMessage.new_request(req)
        frame = msg.to_frame()
        r = ByteReader(frame)
        payload_len = r.read_i32()
        payload = r.read_raw(payload_len)
        header, body = decode_request_header(payload)
        assert header.api_key == ApiVersionsRequest.API_KEY
        decoded = ApiVersionsRequest.decode(body, header.api_version)
        assert decoded.client_version == "9.9.9"

    def test_api_versions_response(self):
        resp = ApiVersionsResponse(
            api_keys=[ApiVersionKey(0, 0, 3), ApiVersionKey(1003, 0, 5)]
        )
        w = ByteWriter()
        resp.encode(w, 0)
        out = ApiVersionsResponse.decode(ByteReader(w.bytes()), 0)
        assert out.lookup_version(1003) == 5
        assert out.lookup_version(42) is None

    def test_api_error(self):
        for err in [ApiError.ok(), ApiError(ErrorCode.TOPIC_NOT_FOUND, "no such topic")]:
            w = ByteWriter()
            err.encode(w)
            out = ApiError.decode(ByteReader(w.bytes()))
            assert out.code == err.code
            assert out.message == err.message


class TestPurePythonCodecs:
    """Bundled lz4/snappy (protocol/lz4_py.py, snappy_py.py): roundtrip
    fuzz plus hand-assembled spec vectors, so a stream produced by any
    compliant encoder (the reference's snap/lz4_flex crates included)
    decodes here."""

    def test_snappy_spec_vectors(self):
        from fluvio_tpu.protocol import snappy_py

        # literal-only stream: varint(5) + tag((5-1)<<2) + bytes
        assert snappy_py.decompress(b"\x05" + bytes([4 << 2]) + b"hello") == b"hello"
        # 1-byte-offset copy (tag 01): "a" then copy len 7 offset 1
        stream = b"\x08" + b"\x00a" + bytes([((7 - 4) << 2) | 1, 1])
        assert snappy_py.decompress(stream) == b"a" * 8
        # 2-byte-offset copy (tag 10): "ab" then copy len 6 offset 2
        stream = b"\x08" + bytes([1 << 2]) + b"ab" + bytes([(6 - 1) << 2 | 2, 2, 0])
        assert snappy_py.decompress(stream) == b"ab" * 4
        # wrong preamble fails closed
        with pytest.raises(snappy_py.SnappyError):
            snappy_py.decompress(b"\x09" + bytes([4 << 2]) + b"hello")

    def test_lz4_block_spec_vector(self):
        from fluvio_tpu.protocol.lz4_py import _decompress_block

        # token: 4 literals, match len 7 (3+4); offset 4 -> "abcd" * repeats
        block = bytes([(4 << 4) | 3]) + b"abcd" + (4).to_bytes(2, "little")
        # trailing literals are required by the spec; append 5 of them
        block += bytes([5 << 4]) + b"zzzzz"
        # 4 literals + 7-byte match at offset 4 ("abcdabc") + 5 literals
        assert _decompress_block(block, 1 << 20) == b"abcd" + b"abcdabc" + b"zzzzz"

    def test_lz4_foreign_frame_with_checksums(self):
        """A frame the way python-lz4/lz4_flex emit it: content size +
        content checksum present — our decoder must verify both."""
        from fluvio_tpu.protocol.lz4_py import MAGIC, xxh32, decompress

        payload = b"hello"
        flg = (1 << 6) | (1 << 5) | (1 << 3) | (1 << 2)  # v1, indep, csize, cchk
        bd = 4 << 4  # 64 KiB block max
        desc = bytes([flg, bd]) + len(payload).to_bytes(8, "little")
        frame = bytearray(MAGIC.to_bytes(4, "little"))
        frame += desc
        frame.append((xxh32(desc) >> 8) & 0xFF)
        frame += (len(payload) | 0x80000000).to_bytes(4, "little")  # raw block
        frame += payload
        frame += (0).to_bytes(4, "little")
        frame += xxh32(payload).to_bytes(4, "little")
        assert decompress(bytes(frame)) == payload
        # flipped content checksum fails closed
        bad = bytearray(frame)
        bad[-1] ^= 0xFF
        from fluvio_tpu.protocol.lz4_py import Lz4Error

        with pytest.raises(Lz4Error):
            decompress(bytes(bad))

    def test_roundtrip_fuzz(self):
        import os as _os
        import random

        from fluvio_tpu.protocol import lz4_py, snappy_py

        rng = random.Random(13)
        cases = [b"", b"x", _os.urandom(3000), b"abc" * 4000]
        for _ in range(10):
            n = rng.randrange(1, 5000)
            alphabet = bytes(range(rng.randrange(2, 30)))
            cases.append(bytes(rng.choice(alphabet) for _ in range(n)))
        for case in cases:
            assert snappy_py.decompress(snappy_py.compress(case)) == case
            assert lz4_py.decompress(lz4_py.compress(case)) == case


class TestNativeCodecs:
    """fluvio_tpu/native/codecs.cpp: wire-compatible with the bundled pure-Python
    lz4/snappy codecs, and memory-safe on malformed input (review round 4
    weak #6 — the fallbacks are correctness-only at ~10-50 MB/s; the
    native library is what a compressed topic's hot path should run)."""

    @staticmethod
    def _mods():
        from fluvio_tpu.protocol import native_codecs

        lz, sn = native_codecs.lz4_module(), native_codecs.snappy_module()
        if lz is None or sn is None:
            pytest.skip("no native toolchain")
        return lz, sn

    def test_cross_impl_roundtrips(self):
        import os as _os
        import random

        from fluvio_tpu.protocol import lz4_py, snappy_py

        lz, sn = self._mods()
        rng = random.Random(7)
        cases = [b"", b"x", b"ab" * 40000, _os.urandom(5000), b"\x00" * 70000]
        for _ in range(10):
            n = rng.randrange(1, 8000)
            alphabet = bytes(range(rng.randrange(2, 40)))
            cases.append(bytes(rng.choice(alphabet) for _ in range(n)))
        for case in cases:
            # native output readable by the pure-Python codecs and back
            assert lz4_py.decompress(lz.compress(case)) == case
            assert lz.decompress(lz4_py.compress(case)) == case
            assert lz.decompress(lz.compress(case)) == case
            assert snappy_py.decompress(sn.compress(case)) == case
            assert sn.decompress(snappy_py.compress(case)) == case
            assert sn.decompress(sn.compress(case)) == case

    def test_malformed_input_errors_cleanly(self):
        import os as _os
        import random

        from fluvio_tpu.protocol.lz4_py import Lz4Error
        from fluvio_tpu.protocol.snappy_py import SnappyError

        lz, sn = self._mods()
        rng = random.Random(29)
        for _ in range(60):
            junk = _os.urandom(rng.randrange(0, 400))
            try:
                lz.decompress(junk)
            except Lz4Error:
                pass
            try:
                sn.decompress(junk)
            except SnappyError:
                pass
        # truncations of a VALID stream must error, never crash
        good_lz = lz.compress(b"fluvio " * 500)
        good_sn = sn.compress(b"fluvio " * 500)
        for cut in range(1, len(good_lz), 37):
            try:
                lz.decompress(good_lz[:cut])
            except Lz4Error:
                pass
        for cut in range(1, len(good_sn), 17):
            try:
                sn.decompress(good_sn[:cut])
            except SnappyError:
                pass

    def test_compression_module_prefers_native(self):
        """With no wheels installed (this image), compress() must route
        lz4/snappy through the native library, not the slow fallback."""
        from fluvio_tpu.protocol import compression as c

        data = b'{"name":"fluvio"}' * 1000
        for codec in (c.Compression.LZ4, c.Compression.SNAPPY):
            assert c.decompress(codec, c.compress(codec, data)) == data
        _, lz4_impl = c.lz4_codec()
        _, snappy_impl = c.snappy_codec()
        if lz4_impl == "python" or snappy_impl == "python":
            pytest.skip("no native toolchain: pure-Python fallback in use")
        assert not c._slow_codecs  # no slow-codec warning fired


# -- one buffer on the way out (PR 39) -----------------------------------------


def _batch_encode_by_parts(b: Batch) -> bytes:
    """The batch's wire form, assembled the long way round: the part
    the CRC covers in a buffer of its own, then the preamble."""
    import struct
    import zlib

    body = (
        struct.pack(
            ">hiqqqhi", b.header.attributes, b.header.last_offset_delta,
            b.header.first_timestamp, b.header.max_time_stamp,
            b.header.producer_id, b.header.producer_epoch,
            b.header.first_sequence,
        )
        + struct.pack(">i", b.records_len())
        + b._encode_record_section()
    )
    return (
        struct.pack(">qiibI", b.base_offset, 4 + 1 + 4 + len(body),
                    b.header.partition_leader_epoch, b.header.magic,
                    zlib.crc32(body) & 0xFFFFFFFF)
        + body
    )


@pytest.mark.parametrize("lead", [0, 7], ids=["empty-writer", "after-other-bytes"])
@pytest.mark.parametrize("raw", [False, True], ids=["records", "raw-slab"])
def test_batch_and_record_set_encode_in_place_equal_the_parts(lead, raw):
    """`Batch.encode` and `RecordSet.encode` write into the caller's
    writer and patch the CRC and the lengths afterwards: the bytes are
    those of the assembly by parts, wherever in the writer they start."""
    import struct

    batches = []
    for base in (40, 90):
        b = Batch.from_records(
            [Record(value=b"v%d" % (base + i), key=b"k" if i else None)
             for i in range(3)],
            base_offset=base, first_timestamp=1_700_000_000_000,
        )
        if raw:
            w = ByteWriter()
            b.encode(w)
            b = Batch.decode(ByteReader(w.bytes()), parse_records=False)
            assert b.raw_records is not None
        batches.append(b)
    w = ByteWriter()
    w.write_raw(b"\xAA" * lead)
    batches[0].encode(w)
    assert w.bytes() == b"\xAA" * lead + _batch_encode_by_parts(batches[0])
    rs = RecordSet()
    for b in batches:
        rs.add(b)
    w = ByteWriter()
    w.write_raw(b"\xAA" * lead)
    rs.encode(w)
    body = b"".join(map(_batch_encode_by_parts, batches))
    assert w.bytes() == b"\xAA" * lead + struct.pack(">i", len(body)) + body
    back = RecordSet.decode(ByteReader(w.bytes()[lead:]))
    assert [r.value for b in back.batches for r in b.memory_records()] == [
        b"v40", b"v41", b"v42", b"v90", b"v91", b"v92"]
    # the CRC the decoder checks is the patched one
    Batch.decode(ByteReader(_batch_encode_by_parts(batches[1])), check_crc=True)
    w = ByteWriter()
    batches[1].encode(w)
    Batch.decode(ByteReader(w.bytes()), check_crc=True)


def test_response_frame_buffer_is_the_frame():
    """`ResponseMessage.frame_buffer` is the length-prefixed frame in the
    one buffer it was encoded into: what `to_frame` and the payload the
    sink used to prefix spell."""
    import struct

    from fluvio_tpu.protocol.api import ResponseMessage
    from fluvio_tpu.schema.spu import FetchOffsetsResponse

    msg = ResponseMessage(77, FetchOffsetsResponse())
    payload = msg.encode_payload(0)
    frame = msg.frame_buffer(0)
    assert isinstance(frame, bytearray)
    assert bytes(frame) == struct.pack(">i", len(payload)) + payload
    assert msg.to_frame(0) == bytes(frame)


def test_large_buffers_stay_on_the_heap_where_libc_is_glibc():
    """Best effort and repeatable: True on glibc (the settings took),
    False elsewhere, never an exception."""
    import platform

    from fluvio_tpu.spu.server import keep_large_buffers_on_heap

    took = keep_large_buffers_on_heap()
    assert took == keep_large_buffers_on_heap()
    if platform.libc_ver()[0] == "glibc":
        assert took is True
