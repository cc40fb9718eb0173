"""Native C++ batch loader vs the PIL reference path."""

import io
import os

import numpy as np
import pytest
from PIL import Image

from dml_tpu.native.loader import get_loader


def _write_jpeg(path, arr, quality=95):
    Image.fromarray(arr).save(path, "JPEG", quality=quality)


@pytest.fixture(scope="module")
def loader():
    l = get_loader()
    if l is None:
        pytest.skip("native loader unavailable (no g++/libjpeg)")
    return l


def test_decode_no_resize_matches_pil(tmp_path, loader):
    rng = np.random.RandomState(0)
    # JPEG is lossy, but both decoders are libjpeg, so decode at native
    # size must match PIL byte-for-byte
    arr = (rng.rand(64, 64, 3) * 255).astype(np.uint8)
    p = tmp_path / "a.jpeg"
    _write_jpeg(str(p), arr)
    native = loader.decode_batch([str(p)], (64, 64))[0]
    pil = np.asarray(Image.open(p).convert("RGB"), np.uint8)
    np.testing.assert_array_equal(native, pil)


def test_decode_resize_close_to_pil(tmp_path, loader):
    # gradient image: bilinear implementations differ in the corners
    # but must agree closely on smooth content
    h = np.linspace(0, 255, 200, dtype=np.float32)
    arr = np.stack([
        np.tile(h, (160, 1)),
        np.tile(h[::-1], (160, 1)),
        np.full((160, 200), 128, np.float32),
    ], axis=-1).astype(np.uint8)
    p = tmp_path / "g.jpeg"
    _write_jpeg(str(p), arr)
    native = loader.decode_batch([str(p)], (96, 96))[0].astype(np.int16)
    pil = np.asarray(
        Image.open(p).convert("RGB").resize((96, 96), Image.BILINEAR), np.uint8
    ).astype(np.int16)
    assert np.abs(native - pil).mean() < 4.0
    assert native.shape == (96, 96, 3)


def test_batch_and_dct_scaling(tmp_path, loader):
    rng = np.random.RandomState(1)
    paths = []
    for i, side in enumerate([64, 640, 1280]):  # forces scale_denom 1/2/4+
        arr = rng.randint(0, 255, (side, side, 3), np.uint8)
        p = tmp_path / f"s{i}.jpeg"
        _write_jpeg(str(p), arr)
        paths.append(str(p))
    out = loader.decode_batch(paths, (64, 64), n_threads=2)
    assert out.shape == (3, 64, 64, 3)
    assert out.dtype == np.uint8


def test_error_reports_filename(tmp_path, loader):
    p = tmp_path / "bad.jpeg"
    p.write_bytes(b"not a jpeg at all")
    with pytest.raises(RuntimeError, match="bad.jpeg"):
        loader.decode_batch([str(p)], (32, 32))


def test_load_images_uses_native_and_falls_back(tmp_path):
    from dml_tpu.models.preprocess import load_images

    rng = np.random.RandomState(2)
    good = tmp_path / "ok.jpeg"
    _write_jpeg(str(good), rng.randint(0, 255, (50, 50, 3), np.uint8))
    out = load_images([str(good)], (32, 32))
    assert out.shape == (1, 32, 32, 3)

    # fake-jpeg bytes under a .jpeg name: native decode fails, PIL
    # fallback must also fail the same way a PIL-only path would...
    png = tmp_path / "really_png.jpeg"
    img = Image.fromarray(rng.randint(0, 255, (40, 40, 3), np.uint8))
    buf = io.BytesIO()
    img.save(buf, "PNG")
    png.write_bytes(buf.getvalue())
    # ...except PIL sniffs content, so the PNG decodes fine:
    out = load_images([str(png)], (32, 32))
    assert out.shape == (1, 32, 32, 3)


def test_library_is_keyed_by_source_and_flags(tmp_path, monkeypatch, loader):
    """A `.so` whose key does not match this source and build command is
    never loaded — not a stale `libdmlloader.so` trusted by mtime, not a
    binary that rode in with a copy of the tree from another machine."""
    import shutil

    from dml_tpu.native import loader as nl

    built = nl.lib_path()
    assert os.path.exists(built)  # the `loader` fixture built it
    assert "-march=native" not in nl._build_cmd("x.so")

    # a private source dir holding an edited source, the old name and a
    # library under ANOTHER key, each newer than the source
    src_dir = tmp_path / "native"
    src_dir.mkdir()
    with open(nl._SRC) as f:
        (src_dir / "dataloader.cpp").write_text(f.read() + "\n// edited\n")
    decoys = [src_dir / "libdmlloader.so",
              src_dir / "libdmlloader-0123456789abcdef.so"]
    for d in decoys:
        d.write_bytes(b"not a shared object")
    monkeypatch.setattr(nl, "_SRC_DIR", str(src_dir))
    monkeypatch.setattr(nl, "_SRC", str(src_dir / "dataloader.cpp"))
    monkeypatch.setattr(nl, "_loader", None)
    monkeypatch.setattr(nl, "_failed", False)

    want = nl.lib_path()
    assert os.path.basename(want) != os.path.basename(built)  # source changed
    assert want not in [str(d) for d in decoys]
    got = nl.get_loader()
    assert got is not None and os.path.exists(want)
    # built fresh from the source at hand; the decoys were swept, not loaded
    assert not any(d.exists() for d in decoys)
    shutil.rmtree(src_dir)


def test_failed_build_warns_once_with_compiler_stderr(
        tmp_path, monkeypatch, caplog):
    from dml_tpu.native import loader as nl

    src_dir = tmp_path / "native"
    src_dir.mkdir()
    (src_dir / "dataloader.cpp").write_text("this is not C++\n")
    monkeypatch.setattr(nl, "_SRC_DIR", str(src_dir))
    monkeypatch.setattr(nl, "_SRC", str(src_dir / "dataloader.cpp"))
    monkeypatch.setattr(nl, "_loader", None)
    monkeypatch.setattr(nl, "_failed", False)
    with caplog.at_level("WARNING", logger=nl.__name__):
        assert nl.get_loader() is None
        assert nl.get_loader() is None  # latched: no second build, no second warning
    warnings = [r for r in caplog.records if r.levelname == "WARNING"]
    assert len(warnings) == 1
    assert "falls back to PIL" in warnings[0].getMessage()
    assert "error" in warnings[0].getMessage()  # the compiler's own words
