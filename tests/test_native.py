"""Native batch-packer tests (C++ lib vs numpy/jax references)."""

import numpy as np
import pytest

import jax

from sparkdl_tpu import native


@pytest.fixture(scope="module", autouse=True)
def built():
    if not native.ensure_built():
        pytest.skip("native toolchain unavailable")


def test_abi_available():
    assert native.available()


def test_pack_batch_exact_no_resize():
    rng = np.random.RandomState(0)
    b = rng.randint(0, 256, (4, 5, 6, 3)).astype(np.uint8)
    out = native.pack_batch(b, flip_bgr=True, scale=1 / 127.5, offset=-1.0)
    assert out.dtype == np.float32
    want = b[..., ::-1].astype(np.float32) / 127.5 - 1.0
    assert np.allclose(out, want, atol=1e-6)


def test_pack_batch_matches_jax_resize():
    rng = np.random.RandomState(1)
    for (h, w), (oh, ow) in [((10, 12), (8, 8)), ((7, 5), (16, 16)),
                             ((20, 20), (8, 14))]:
        src = rng.randint(0, 256, (2, h, w, 3)).astype(np.uint8)
        nat = native.pack_batch(src, oh, ow)
        ref = np.asarray(jax.image.resize(
            src.astype(np.float32), (2, oh, ow, 3), method="bilinear"))
        assert np.abs(nat - ref).max() < 1e-3, ((h, w), (oh, ow))


def test_pack_images_variable_sizes():
    rng = np.random.RandomState(2)
    hs, ws = [9, 17, 8], [11, 6, 8]
    bufs = [rng.randint(0, 256, (h, w, 3)).astype(np.uint8).tobytes()
            for h, w in zip(hs, ws)]
    out = native.pack_images(bufs, hs, ws, 3, 8, 8, flip_bgr=True)
    assert out.shape == (3, 8, 8, 3)
    for i, (h, w) in enumerate(zip(hs, ws)):
        src = np.frombuffer(bufs[i], np.uint8).reshape(h, w, 3)
        ref = np.asarray(jax.image.resize(
            src[..., ::-1].astype(np.float32), (8, 8, 3), method="bilinear"))
        assert np.abs(out[i] - ref).max() < 1e-3


def test_bgra_flip_native_and_python_paths_agree():
    """c=4 flip must be BGRA→RGBA (alpha preserved) on EVERY path."""
    from sparkdl_tpu.image import imageIO

    rng = np.random.RandomState(9)
    arr = rng.randint(0, 256, (5, 5, 4)).astype(np.uint8)
    structs = [imageIO.imageArrayToStruct(arr)]
    nat = imageIO.structsToNHWC(structs)  # native path (float32 + uint8)
    py = imageIO.structsToNHWC(structs, dtype=np.float64).astype(np.float32)
    np.testing.assert_allclose(nat, py)
    assert np.allclose(nat[0][..., 3], arr[..., 3])   # alpha stays channel 3
    assert np.allclose(nat[0][..., 0], arr[..., 2])   # B<->R swapped
    # round-trip: NHWC (RGBA) → structs (BGRA) → NHWC
    back = imageIO.structsToNHWC(imageIO.nhwcToStructs(
        nat.astype(np.uint8)))
    np.testing.assert_allclose(back, nat)


def test_pack_images_bgra_alpha_preserved():
    rng = np.random.RandomState(3)
    b = rng.randint(0, 256, (2, 4, 4, 4)).astype(np.uint8)
    out = native.pack_batch(b, flip_bgr=True)
    assert np.allclose(out[..., 3], b[..., 3])
    assert np.allclose(out[..., 0], b[..., 2])
    assert np.allclose(out[..., 2], b[..., 0])


def test_pack_images_grayscale():
    rng = np.random.RandomState(4)
    b = rng.randint(0, 256, (3, 6, 6, 1)).astype(np.uint8)
    out = native.pack_batch(b, flip_bgr=True)  # flip is a no-op for c=1
    assert np.allclose(out, b.astype(np.float32))


def test_bad_buffer_size_raises():
    with pytest.raises(ValueError, match="expected"):
        native.pack_images([b"abc"], [4], [4], 3, 4, 4)


def test_empty_batch():
    out = native.pack_images([], [], [], 3, 4, 4)
    assert out.shape == (0, 4, 4, 3)


def test_numpy_fallback_agrees_uniform():
    rng = np.random.RandomState(5)
    b = rng.randint(0, 256, (3, 5, 5, 3)).astype(np.uint8)
    nat = native.pack_batch(b, flip_bgr=True, scale=2.0, offset=1.0)
    ref = np.empty_like(nat)
    native._pack_images_numpy([b[i] for i in range(3)], [5] * 3, [5] * 3, 3,
                              ref, True, 2.0, 1.0)
    assert np.allclose(nat, ref, atol=1e-5)


def test_image_column_uses_native_path(monkeypatch):
    """imageColumnToNHWC's output must agree with the pure-python path."""
    import pyarrow as pa

    from sparkdl_tpu.image import imageIO

    rng = np.random.RandomState(6)
    structs = [imageIO.imageArrayToStruct(
        rng.randint(0, 256, (7, 7, 3)).astype(np.uint8)) for _ in range(4)]
    col = pa.array(structs, type=imageIO.imageSchema)
    monkeypatch.setenv("SPARKDL_TPU_NATIVE", "1")
    fast = imageIO.imageColumnToNHWC(col)
    monkeypatch.setenv("SPARKDL_TPU_NATIVE", "0")
    slow = imageIO.imageColumnToNHWC(col)
    assert np.allclose(fast, slow, atol=1e-5)


def test_pack_images_rejects_nonuint8_arrays():
    with pytest.raises(TypeError, match="uint8"):
        native.pack_images([np.ones((4, 4, 3), np.float32)], [4], [4],
                           3, 4, 4)


def test_pack_images_u8_output_exact_and_rounds():
    """dtype=uint8 output: exact passthrough when no resize; rounded (<=0.5
    level) match of the float path when resizing — the u8 feed ships 4x
    fewer bytes to the device (round-3 perf fix)."""
    rng = np.random.RandomState(3)
    img = rng.randint(0, 256, size=(20, 30, 3)).astype(np.uint8)
    same = native.pack_images([img.tobytes()], [20], [30], 3, 20, 30,
                              flip_bgr=True, dtype=np.uint8)
    assert same.dtype == np.uint8
    np.testing.assert_array_equal(same[0], img[:, :, ::-1])

    f32 = native.pack_images([img.tobytes()], [20], [30], 3, 11, 17,
                             flip_bgr=True)
    u8 = native.pack_images([img.tobytes()], [20], [30], 3, 11, 17,
                            flip_bgr=True, dtype=np.uint8)
    assert np.abs(f32[0] - u8[0].astype(np.float32)).max() <= 0.5 + 1e-3


def test_pack_images_rejects_bad_dtype():
    with pytest.raises(TypeError):
        native.pack_images([b"\x00" * 3], [1], [1], 3, 1, 1,
                           dtype=np.float64)


def test_ensure_built_thread_safe_single_make(monkeypatch, tmp_path):
    """Concurrent first-use must run at most one build, and a make that
    produces no .so must be reported as a failure (ADVICE r1 item 2)."""
    import threading
    import sparkdl_tpu.native as nat

    calls = []
    lock_probe = threading.Barrier(4, timeout=10)

    def fake_run(*a, **kw):
        calls.append(a)
        class R:
            returncode = 0
        return R()

    monkeypatch.setattr(nat, "_SO_PATH", str(tmp_path / "never_built.so"))
    monkeypatch.setattr(nat, "_failure", None)
    monkeypatch.setattr(nat.subprocess, "run", fake_run)

    results = []

    def worker():
        lock_probe.wait()
        results.append(nat.ensure_built())

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # make "succeeded" but produced no .so -> failure, and only ONE make ran
    # (the rest short-circuited on _failure under the lock).
    assert results == [False] * 4
    assert len(calls) == 1
