"""Native C++ PNG decoder tests: builds on this machine, matches PIL bit-for-bit
(both divide the same uint8 by 255), handles errors, and releases the GIL enough to
scale with threads."""

import os

import numpy as np
import pytest
from PIL import Image

from tensorflowdistributedlearning_tpu.native import decode_png_batch, native_available
from tensorflowdistributedlearning_tpu.native.loader import _decode_pil


@pytest.fixture(scope="module")
def png_files(tmp_path_factory):
    root = tmp_path_factory.mktemp("pngs")
    rng = np.random.default_rng(7)
    paths = []
    for i in range(12):
        arr = rng.integers(0, 256, (24, 24), dtype=np.uint8)
        p = str(root / f"g{i}.png")
        Image.fromarray(arr).save(p)
        paths.append(p)
    # one RGB file for the luma-conversion path
    rgb = rng.integers(0, 256, (24, 24, 3), dtype=np.uint8)
    rgb_path = str(root / "rgb.png")
    Image.fromarray(rgb).save(rgb_path)
    return paths, rgb_path


def test_native_builds_here():
    # this image ships g++ and libpng; the build must succeed, not silently fall back
    assert native_available()


def test_native_matches_pil_grayscale(png_files):
    paths, _ = png_files
    native = decode_png_batch(paths, 24, 24, channels=1)
    pil = _decode_pil(paths, 24, 24, channels=1)
    np.testing.assert_array_equal(native, pil)
    assert native.dtype == np.float32
    assert native.min() >= 0.0 and native.max() <= 1.0


def test_native_rgb_to_gray_close_to_pil(png_files):
    _, rgb_path = png_files
    native = decode_png_batch([rgb_path], 24, 24, channels=1)
    pil = _decode_pil([rgb_path], 24, 24, channels=1)
    # PIL rounds the luma to uint8 before /255; the native path keeps float precision
    assert np.abs(native - pil).max() < 2.0 / 255.0


def test_gray_broadcast_to_three_channels(png_files):
    paths, _ = png_files
    out = decode_png_batch(paths[:2], 24, 24, channels=3)
    np.testing.assert_array_equal(out[..., 0], out[..., 1])
    np.testing.assert_array_equal(out[..., 0], out[..., 2])


def test_wrong_shape_raises(png_files):
    paths, _ = png_files
    with pytest.raises(ValueError, match="decode failed"):
        decode_png_batch(paths[:1], 32, 32, channels=1)


def test_missing_file_raises(tmp_path):
    with pytest.raises(ValueError, match="decode failed"):
        decode_png_batch([str(tmp_path / "nope.png")], 8, 8)


def test_empty_input():
    out = decode_png_batch([], 8, 8)
    assert out.shape == (0, 8, 8, 1)


def test_interlaced_png_decodes_correctly(tmp_path):
    # Adam7-interlaced files must match PIL (png_read_image runs all passes)
    rng = np.random.default_rng(3)
    arr = rng.integers(0, 256, (24, 24), dtype=np.uint8)
    p = str(tmp_path / "interlaced.png")
    Image.fromarray(arr).save(p, interlace=True)
    native = decode_png_batch([p], 24, 24, channels=1)
    pil = _decode_pil([p], 24, 24, channels=1)
    np.testing.assert_array_equal(native, pil)


def test_multithreaded_decode_consistent(png_files):
    paths, _ = png_files
    one = decode_png_batch(paths, 24, 24, n_threads=1)
    many = decode_png_batch(paths, 24, 24, n_threads=8)
    np.testing.assert_array_equal(one, many)


# ---------------------------------------------------------------------------
# decode_image_batch: PNG/JPEG at any size, antialiased bilinear resize
# ---------------------------------------------------------------------------


@pytest.fixture()
def mixed_files(tmp_path):
    from tensorflowdistributedlearning_tpu.native import decode_image_batch  # noqa: F401

    rng = np.random.default_rng(7)
    paths = []
    for i, (h, w) in enumerate([(90, 120), (64, 64), (300, 201), (17, 33)]):
        arr = rng.integers(0, 256, (h, w, 3), dtype=np.uint8)
        if i % 2:
            p = str(tmp_path / f"im{i}.jpg")
            Image.fromarray(arr).save(p, quality=98)
        else:
            p = str(tmp_path / f"im{i}.png")
            Image.fromarray(arr).save(p)
        paths.append(p)
    return paths


def test_decode_image_batch_matches_pil_resize(mixed_files):
    """The ImageNet-class decode path (variable-size JPEG+PNG, triangle-filter
    bilinear) agrees with PIL's convert+resize to within uint8 rounding."""
    from tensorflowdistributedlearning_tpu.native import decode_image_batch
    from tensorflowdistributedlearning_tpu.native.loader import _decode_pil_resize

    out = decode_image_batch(mixed_files, 32, 48, channels=3)
    ref = _decode_pil_resize(mixed_files, 32, 48, 3)
    assert out.shape == (4, 32, 48, 3)
    assert np.abs(out - ref).max() < 0.02  # PIL rounds to uint8 per stage


def test_decode_image_batch_gray(mixed_files):
    from tensorflowdistributedlearning_tpu.native import decode_image_batch
    from tensorflowdistributedlearning_tpu.native.loader import _decode_pil_resize

    out = decode_image_batch(mixed_files, 24, 24, channels=1)
    ref = _decode_pil_resize(mixed_files, 24, 24, 1)
    assert out.shape == (4, 24, 24, 1)
    assert np.abs(out - ref).max() < 0.02


def test_decode_image_batch_missing_file(tmp_path):
    """A file the native decoder rejects retries through PIL (per-file
    fallback); a genuinely missing file surfaces PIL's error."""
    from tensorflowdistributedlearning_tpu.native import decode_image_batch

    with pytest.raises(FileNotFoundError):
        decode_image_batch([str(tmp_path / "nope.jpg")], 8, 8)


def test_decode_image_batch_partial_fallback(tmp_path):
    """One undecodable file in a batch falls back to PIL alone; the rest still
    decode natively and every row is correct."""
    from tensorflowdistributedlearning_tpu.native import decode_image_batch
    from tensorflowdistributedlearning_tpu.native.loader import _decode_pil_resize

    rng = np.random.default_rng(9)
    paths = []
    for i in range(3):
        arr = rng.integers(0, 256, (40, 40, 3), dtype=np.uint8)
        p = str(tmp_path / f"ok{i}.png")
        Image.fromarray(arr).save(p)
        paths.append(p)
    # a BMP with a lying extension: native sniff fails, PIL handles it
    odd = str(tmp_path / "odd.png")
    Image.fromarray(
        rng.integers(0, 256, (40, 40, 3), dtype=np.uint8)
    ).save(odd, format="BMP")
    paths.insert(1, odd)
    out = decode_image_batch(paths, 16, 16, channels=3)
    ref = _decode_pil_resize(paths, 16, 16, 3)
    assert out.shape == (4, 16, 16, 3)
    assert np.abs(out - ref).max() < 0.02


def test_imagefolder_accepts_jpeg(tmp_path):
    """ImageFolder scans and decodes JPEG class dirs (the real ImageNet format)."""
    from tensorflowdistributedlearning_tpu.data import imagefolder

    rng = np.random.default_rng(8)
    for k in range(2):
        d = tmp_path / f"class{k}"
        d.mkdir()
        for i in range(3):
            arr = rng.integers(0, 256, (40 + 10 * i, 50, 3), dtype=np.uint8)
            Image.fromarray(arr).save(str(d / f"im{i}.jpg"), quality=95)
    ds = imagefolder.ImageFolder(str(tmp_path), (32, 32), channels=3)
    assert len(ds) == 6
    assert ds.num_classes == 2
    batch = next(imagefolder.train_batches(ds, 4, seed=0, steps=1))
    assert batch["images"].shape == (4, 32, 32, 3)


def test_library_is_named_by_a_hash_of_its_source(tmp_path):
    """A copied tree does not promise mtimes, so freshness is not a
    timestamp: the library built from this source lives at a path that
    carries the source's hash, and other source means another path."""
    from tensorflowdistributedlearning_tpu.native import loader

    a, b = tmp_path / "a.cc", tmp_path / "b.cc"
    a.write_text("int f() { return 1; }\n")
    b.write_text("int f() { return 2; }\n")
    path_a = loader._library_path(str(a), "libx")
    assert path_a == loader._library_path(str(a), "libx")  # content, not time
    assert path_a != loader._library_path(str(b), "libx")
    assert os.path.dirname(path_a) == loader._BUILD_DIR
    assert os.path.basename(path_a).startswith("libx-")


def test_a_stale_library_under_the_old_name_is_never_loaded(monkeypatch):
    """What an older checkout left in _build/ under the unhashed name — built
    from who knows which source — is not what the loader looks for."""
    from tensorflowdistributedlearning_tpu.native import loader

    stale = os.path.join(loader._BUILD_DIR, "libtfdl_io.so")
    os.makedirs(loader._BUILD_DIR, exist_ok=True)
    created = not os.path.exists(stale)
    if created:
        with open(stale, "wb") as f:
            f.write(b"not a shared library")
    try:
        loaded = []
        real_cdll = loader.ctypes.CDLL
        monkeypatch.setattr(
            loader.ctypes, "CDLL",
            lambda path, *a, **k: loaded.append(path) or real_cdll(path, *a, **k),
        )
        monkeypatch.setattr(loader, "_tried", False)
        monkeypatch.setattr(loader, "_lib", None)
        assert loader.native_available()
        assert loaded == [loader._library_path(loader._SRC, "libtfdl_io")]
        assert stale not in loaded
    finally:
        if created:
            os.remove(stale)
