"""Native C++ IO library tests: build, byte-compat with the python
RecordIO implementation, CSV parser, and the io-tier wiring."""
import os
import struct

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import recordio
from mxnet_tpu.lib import nativelib

pytestmark = pytest.mark.skipif(
    not nativelib.available(),
    reason="native toolchain unavailable (python fallback covers behavior)")

_MAGIC = struct.pack("<I", 0xCED7230A)


def test_staleness_is_keyed_on_source_content(tmp_path, monkeypatch):
    """The cached .so travels with copies of the tree (mtimes do not):
    it is current exactly when its stamp holds the source's sha256."""
    so = tmp_path / "lib.so"
    so.write_bytes(b"not really a library")
    monkeypatch.setattr(nativelib, "_SO", str(so))
    monkeypatch.setattr(nativelib, "_STAMP", str(so) + ".sha256")
    assert nativelib._stale()                   # no stamp
    (tmp_path / "lib.so.sha256").write_text("0" * 64)
    assert nativelib._stale()                   # built from other source
    (tmp_path / "lib.so.sha256").write_text(nativelib._src_digest())
    assert not nativelib._stale()
    so.unlink()
    assert nativelib._stale()                   # stamp without a binary


class TestNativeRecordIO:
    def test_roundtrip_including_multipart(self, tmp_path):
        path = str(tmp_path / "t.rec")
        payloads = [b"hello", b"x" * 1000, _MAGIC + b"lead",
                    b"a" + _MAGIC + b"b" + _MAGIC + b"c", b""]
        w = nativelib.NativeRecordWriter(path)
        for p in payloads:
            w.write(p)
        w.close()
        r = nativelib.NativeRecordReader(path)
        offs = r.index()
        assert len(offs) == len(payloads)
        assert [r.read_at(o) for o in offs] == payloads

    def test_native_write_python_read(self, tmp_path):
        path = str(tmp_path / "t.rec")
        payloads = [b"one", _MAGIC * 3, b"two" + _MAGIC]
        w = nativelib.NativeRecordWriter(path)
        for p in payloads:
            w.write(p)
        w.close()
        rd = recordio.MXRecordIO(path, "r")
        got = []
        while True:
            s = rd.read()
            if s is None:
                break
            got.append(s)
        assert got == payloads

    def test_python_write_native_read(self, tmp_path):
        path = str(tmp_path / "t.rec")
        payloads = [b"alpha", b"beta" + _MAGIC + b"gamma"]
        wr = recordio.MXRecordIO(path, "w")
        for p in payloads:
            wr.write(p)
        wr.close()
        r = nativelib.NativeRecordReader(path)
        assert [r.read_at(o) for o in r.index()] == payloads

    def test_corrupt_file_detected(self, tmp_path):
        path = str(tmp_path / "bad.rec")
        with open(path, "wb") as f:
            f.write(b"\x00" * 64)
        r = nativelib.NativeRecordReader(path)
        with pytest.raises(IOError):
            r.index()


class TestNativeCSV:
    def test_parse_matches_numpy(self, tmp_path):
        path = str(tmp_path / "d.csv")
        rng = np.random.RandomState(0)
        ref = rng.randn(20, 7).astype(np.float32)
        np.savetxt(path, ref, delimiter=",", fmt="%.6g")
        out = nativelib.csv_load(path)
        ref2 = np.loadtxt(path, delimiter=",", dtype=np.float32, ndmin=2)
        np.testing.assert_array_equal(out, ref2)

    def test_csviter_uses_native(self, tmp_path):
        from mxnet_tpu.io import CSVIter
        path = str(tmp_path / "d.csv")
        lpath = str(tmp_path / "l.csv")
        data = np.arange(24, dtype=np.float32).reshape(6, 4)
        np.savetxt(path, data, delimiter=",", fmt="%g")
        np.savetxt(lpath, np.arange(6, dtype=np.float32), delimiter=",",
                   fmt="%g")
        it = CSVIter(path, (4,), label_csv=lpath, batch_size=3)
        batch = next(it)
        np.testing.assert_array_equal(batch.data[0].asnumpy(), data[:3])

    def test_header_csv_raises(self, tmp_path):
        path = str(tmp_path / "h.csv")
        with open(path, "w") as f:
            f.write("x,y,z\n1,2,3\n")
        with pytest.raises(ValueError):
            nativelib.csv_load(path)

    def test_runtime_reports_native_io(self):
        feats = mx.runtime.Features()
        assert feats.is_enabled("NATIVE_IO")


class TestImageRecordIterNativeScan:
    def test_no_idx_scan_uses_native(self, tmp_path):
        import cv2
        from mxnet_tpu.io import ImageRecordIter
        rec_path = str(tmp_path / "imgs.rec")
        w = recordio.MXRecordIO(rec_path, "w")
        rng = np.random.RandomState(0)
        for i in range(10):
            img = rng.randint(0, 255, (20, 20, 3)).astype(np.uint8)
            header = recordio.IRHeader(0, float(i % 3), i, 0)
            w.write(recordio.pack_img(header, img, img_fmt=".png"))
        w.close()
        it = ImageRecordIter(rec_path, (3, 16, 16), batch_size=5)
        assert it._native is not None          # C++ scanner active
        batch = it.next()
        assert batch.data[0].shape == (5, 3, 16, 16)
        labels = batch.label[0].asnumpy()
        assert set(labels) <= {0.0, 1.0, 2.0}


class TestNativeJpegDecodeTier:
    """The threaded C++ JPEG batch decoder (nativelib.cc mxjpeg_*)."""

    def _jpeg(self, rng, hw=(300, 400), quality=92):
        import cv2
        img = rng.randint(0, 255, hw + (3,), dtype=np.uint8)
        return img, cv2.imencode(
            ".jpg", img[:, :, ::-1],
            [cv2.IMWRITE_JPEG_QUALITY, quality])[1].tobytes()

    def test_decode_batch_matches_cv2_reference(self):
        import cv2
        from mxnet_tpu.lib import nativelib
        if not nativelib.jpeg_available():
            pytest.skip("no libjpeg on this host")
        rng = np.random.RandomState(0)
        imgs, bufs = zip(*[self._jpeg(rng) for _ in range(4)])
        cy = np.full(4, -1.0, np.float32)      # center-crop sentinel
        mir = np.zeros(4, np.uint8)
        out, status = nativelib.decode_jpeg_batch(
            list(bufs), 256, 224, 224, cy, cy, mir, 2)
        assert status.tolist() == [0, 0, 0, 0]
        assert out.shape == (4, 3, 224, 224) and out.dtype == np.uint8
        for i, buf in enumerate(bufs):
            ref = cv2.imdecode(np.frombuffer(buf, np.uint8),
                               cv2.IMREAD_COLOR)[:, :, ::-1]
            h, w = ref.shape[:2]
            s = 256.0 / min(h, w)
            r = cv2.resize(ref, (int(w * s + 0.5), int(h * s + 0.5)))
            y0 = (r.shape[0] - 224) // 2
            x0 = (r.shape[1] - 224) // 2
            want = r[y0:y0 + 224, x0:x0 + 224].transpose(2, 0, 1)
            diff = np.abs(out[i].astype(int) - want.astype(int)).mean()
            # DCT-reduced decode + independent bilinear: small pixel
            # noise vs the full-decode cv2 reference is expected
            assert diff < 6.0, (i, diff)

    def test_mirror_and_integer_crop(self):
        from mxnet_tpu.lib import nativelib
        if not nativelib.jpeg_available():
            pytest.skip("no libjpeg on this host")
        rng = np.random.RandomState(1)
        _img, buf = self._jpeg(rng, hw=(256, 256))
        cy = np.full(1, -1.0, np.float32)
        plain, s1 = nativelib.decode_jpeg_batch(
            [buf], 0, 224, 224, cy, cy, np.zeros(1, np.uint8), 1)
        flipped, s2 = nativelib.decode_jpeg_batch(
            [buf], 0, 224, 224, cy, cy, np.ones(1, np.uint8), 1)
        assert s1[0] == 0 and s2[0] == 0
        np.testing.assert_array_equal(plain[0], flipped[0][:, :, ::-1])

    def test_bad_payload_reports_status_not_crash(self):
        from mxnet_tpu.lib import nativelib
        if not nativelib.jpeg_available():
            pytest.skip("no libjpeg on this host")
        rng = np.random.RandomState(2)
        _img, good = self._jpeg(rng)
        bad = b"\xff\xd8 not really a jpeg"
        cy = np.full(2, -1.0, np.float32)
        out, status = nativelib.decode_jpeg_batch(
            [bad, good], 256, 64, 64, cy, cy, np.zeros(2, np.uint8), 2)
        assert status[0] == 1 and status[1] == 0

    def test_iterator_mixed_shard_falls_back_per_image(self, tmp_path):
        from mxnet_tpu.io import ImageRecordIter
        from mxnet_tpu.lib import nativelib
        if not nativelib.jpeg_available():
            pytest.skip("no libjpeg on this host")
        rec_path = str(tmp_path / "mix.rec")
        w = recordio.MXIndexedRecordIO(rec_path + ".idx", rec_path, "w")
        rng = np.random.RandomState(3)
        for i in range(12):
            img = rng.randint(0, 255, (300, 400, 3), np.uint8)
            fmt = ".jpg" if i % 3 else ".png"     # every 3rd is PNG
            w.write_idx(i, recordio.pack_img(
                recordio.IRHeader(0, float(i), i, 0), img, quality=90,
                img_fmt=fmt))
        w.close()
        it = ImageRecordIter(rec_path, (3, 224, 224), batch_size=6,
                             shuffle=False, resize=256)
        labels = []
        while True:
            try:
                b = it.next()
            except StopIteration:
                break
            d = b.data[0].asnumpy()
            assert d.shape == (6, 3, 224, 224)
            assert np.isfinite(d).all() and d.max() > 10
            labels += list(b.label[0].asnumpy())
        assert it._native_jpeg                    # probe stayed on
        assert labels == [float(i) for i in range(12)]

    def test_iterator_png_shard_disables_probe(self, tmp_path):
        from mxnet_tpu.io import ImageRecordIter
        from mxnet_tpu.lib import nativelib
        if not nativelib.jpeg_available():
            pytest.skip("no libjpeg on this host")
        rec_path = str(tmp_path / "png.rec")
        w = recordio.MXIndexedRecordIO(rec_path + ".idx", rec_path, "w")
        rng = np.random.RandomState(4)
        for i in range(6):
            img = rng.randint(0, 255, (64, 64, 3), np.uint8)
            w.write_idx(i, recordio.pack_img(
                recordio.IRHeader(0, float(i), i, 0), img,
                img_fmt=".png"))
        w.close()
        it = ImageRecordIter(rec_path, (3, 32, 32), batch_size=6,
                             shuffle=False)
        b = it.next()
        assert b.data[0].shape == (6, 3, 32, 32)
        assert not it._native_jpeg                # probe disabled
