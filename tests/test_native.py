"""Native C++ library tests: equivalence with the pure-python fallbacks."""

import json
import zlib

import numpy as np
import pytest

from greptimedb_tpu import native
from greptimedb_tpu.utils import snappy


needs_native = pytest.mark.skipif(
    native.lib() is None, reason="native library not built (no toolchain)"
)


@needs_native
class TestNative:
    def test_crc32_matches_zlib(self, rng):
        for n in (0, 1, 7, 8, 9, 1024, 100_000):
            data = bytes(rng.integers(0, 255, n, dtype=np.uint8))
            assert native.crc32(data) == zlib.crc32(data)

    def test_snappy_roundtrip(self, rng):
        for n in (0, 1, 61, 10_000, 300_000):
            data = bytes(rng.integers(0, 255, n, dtype=np.uint8))
            comp = snappy.compress(data)
            got = native.snappy_decompress(comp)
            assert got == data

    def test_snappy_corrupt_raises(self):
        with pytest.raises(ValueError):
            native.snappy_decompress(b"\x10\xff\xff\xff")

    def test_wal_scan_matches_python(self, tmp_path):
        from greptimedb_tpu.storage.wal import FileLogStore, encode_write

        wal = FileLogStore(str(tmp_path / "wal"))
        payloads = {}
        for i in range(20):
            p = encode_write({"v": np.arange(i + 1)})
            payloads[i + 1] = p
            wal.append(i + 1, p)
        wal.close()
        import os

        seg = [f for f in os.listdir(tmp_path / "wal")][0]
        data = open(tmp_path / "wal" / seg, "rb").read()
        spans, good_end = native.wal_scan(data, 5)
        assert [s for s, _o, _l in spans] == list(range(5, 21))
        assert good_end == len(data)
        for seq, off, ln in spans:
            assert data[off:off + ln] == payloads[seq]

    def test_wal_scan_torn_tail(self, tmp_path):
        from greptimedb_tpu.storage.wal import FileLogStore, encode_write

        wal = FileLogStore(str(tmp_path / "wal"))
        wal.append(1, encode_write({"v": np.array([1])}))
        wal.close()
        import os

        seg = [f for f in os.listdir(tmp_path / "wal")][0]
        data = open(tmp_path / "wal" / seg, "rb").read()
        cut = data + b"\x99\x88\x77"
        spans, good_end = native.wal_scan(cut, 0)
        assert len(spans) == 1 and good_end == len(data)

    def test_json_rows_matches_json_dumps(self):
        if getattr(native.lib(), "_gt_no_json", False):
            pytest.skip("libstdc++ without floating-point to_chars")
        cols = [np.array(["a\"b", None, "é"], dtype=object),
                np.array([1, -2, 3]),
                np.array([0.5, np.nan, 1e16]),
                np.array([True, False, True])]
        assert bytes(native.json_rows(cols)) == json.dumps([
            ["a\"b", 1, 0.5, True], [None, -2, None, False],
            ["é", 3, 1e16, True]]).encode()


def test_json_rows_without_the_symbol_is_none(monkeypatch):
    """Like the WAL wrappers on an older .so: None, and the caller keeps
    its pure-python road."""
    if native.lib() is not None:
        monkeypatch.setattr(native.lib(), "_gt_no_json", True, raising=False)
    assert native.json_rows([np.array([1.0])]) is None
