"""Image I/O: the image-struct schema and numpy converters.

Re-creates the behavior of the reference's image layer (expected upstream file
``python/sparkdl/image/imageIO.py`` + Scala ``ImageUtils.scala`` — SURVEY.md
§1-L1/§2.1: image struct schema ``(height, width, nChannels, mode, data)``,
bytes→struct decode, struct↔numpy conversion, resize, ``readImages*``).

TPU-first deltas from the reference design:
- The struct's ``data`` stays raw bytes in Arrow (one contiguous buffer per
  image); batch assembly goes straight from the Arrow binary column into one
  NHWC numpy array (``structsToNHWC``) that is handed to ``jax.device_put`` —
  the per-row Python object churn of the reference's UDF path never happens.
- At-rest layout matches Spark's ImageSchema: OpenCV mode codes AND OpenCV
  channel order — 3/4-channel image data is stored **BGR(A)**, so structs are
  interchangeable with Spark/reference-written data. The NHWC batch builders
  emit RGB by default (the convention every model preprocess here expects)
  and flip at the single batch-assembly point.
"""

from __future__ import annotations

import io
import os
from collections import namedtuple
from typing import Callable, Sequence

import numpy as np
import pyarrow as pa

ImageFields = ["origin", "height", "width", "nChannels", "mode", "data"]

# OpenCV type codes, as used by Spark's ImageSchema (and the reference's
# OCV-type mapping). dtype + channel count → code.
_OcvType = namedtuple("_OcvType", ["name", "ord", "nChannels", "dtype"])

_SUPPORTED_OCV_TYPES = (
    _OcvType(name="CV_8UC1", ord=0, nChannels=1, dtype="uint8"),
    _OcvType(name="CV_8UC3", ord=16, nChannels=3, dtype="uint8"),
    _OcvType(name="CV_8UC4", ord=24, nChannels=4, dtype="uint8"),
    _OcvType(name="CV_32FC1", ord=5, nChannels=1, dtype="float32"),
    _OcvType(name="CV_32FC3", ord=21, nChannels=3, dtype="float32"),
    _OcvType(name="CV_32FC4", ord=29, nChannels=4, dtype="float32"),
)
_OCV_BY_ORD = {t.ord: t for t in _SUPPORTED_OCV_TYPES}
_OCV_BY_KEY = {(t.dtype, t.nChannels): t for t in _SUPPORTED_OCV_TYPES}

imageSchema = pa.struct([
    ("origin", pa.string()),
    ("height", pa.int32()),
    ("width", pa.int32()),
    ("nChannels", pa.int32()),
    ("mode", pa.int32()),
    ("data", pa.binary()),
])


def _swapRB(arr: np.ndarray) -> np.ndarray:
    """Swap channels 0<->2 (BGR(A)<->RGB(A)), preserving alpha — the one
    channel-reorder convention used on every path (incl. the native packer),
    so results don't depend on which path ran."""
    if arr.shape[-1] < 3:
        return arr
    return np.concatenate([arr[..., 2::-1], arr[..., 3:]], axis=-1)


def ocvTypeByMode(mode: int) -> _OcvType:
    try:
        return _OCV_BY_ORD[mode]
    except KeyError:
        raise ValueError(f"Unsupported OpenCV image mode {mode}; supported: "
                         f"{sorted(_OCV_BY_ORD)}") from None


def imageArrayToStruct(array: np.ndarray, origin: str = "") -> dict:
    """HWC numpy array → image struct dict (Arrow-storable)."""
    if array.ndim == 2:
        array = array[:, :, None]
    if array.ndim != 3:
        raise ValueError(f"Expected HW or HWC array, got shape {array.shape}")
    h, w, c = array.shape
    key = (str(array.dtype), c)
    if key not in _OCV_BY_KEY:
        raise ValueError(f"Unsupported dtype/channels {key}; supported: "
                         f"{sorted(_OCV_BY_KEY)}")
    t = _OCV_BY_KEY[key]
    return {
        "origin": origin,
        "height": int(h),
        "width": int(w),
        "nChannels": int(c),
        "mode": t.ord,
        "data": np.ascontiguousarray(array).tobytes(),
    }


def imageStructToArray(struct: dict) -> np.ndarray:
    """Image struct dict → HWC numpy array (dtype per the mode's OCV type)."""
    t = ocvTypeByMode(struct["mode"])
    arr = np.frombuffer(struct["data"], dtype=t.dtype)
    expected = struct["height"] * struct["width"] * struct["nChannels"]
    if arr.size != expected:
        raise ValueError(
            f"Image data has {arr.size} elements, expected {expected} "
            f"({struct['height']}x{struct['width']}x{struct['nChannels']})")
    return arr.reshape(struct["height"], struct["width"], struct["nChannels"])


def decodeImage(data: bytes, origin: str = "") -> dict | None:
    """Compressed image bytes (PNG/JPEG/...) → image struct; None if undecodable
    (matching the reference's drop-bad-images behavior). Stored channel order
    is BGR(A), per the Spark/OpenCV at-rest convention."""
    from PIL import Image
    try:
        img = Image.open(io.BytesIO(data))
        img = _normalize_pil_mode(img)
        arr = np.asarray(img, dtype=np.uint8)
    except Exception:
        return None
    if arr.ndim == 3 and arr.shape[2] >= 3:
        arr = np.ascontiguousarray(_swapRB(arr))  # RGB(A) → BGR(A)
    return imageArrayToStruct(arr, origin=origin)


def _normalize_pil_mode(img):
    if img.mode in ("L",):
        return img
    if img.mode in ("RGBA", "P", "CMYK"):
        return img.convert("RGBA") if img.mode == "RGBA" else img.convert("RGB")
    if img.mode != "RGB":
        return img.convert("RGB")
    return img


def encodePng(struct: dict) -> bytes:
    from PIL import Image
    arr = imageStructToArray(struct)
    if arr.dtype != np.uint8:
        raise ValueError("encodePng requires uint8 image structs")
    if arr.shape[2] >= 3:
        arr = _swapRB(arr)  # stored BGR(A) → RGB(A) for PIL
    buf = io.BytesIO()
    Image.fromarray(arr.squeeze() if arr.shape[2] == 1 else arr).save(
        buf, format="PNG")
    return buf.getvalue()


def resizeImage(struct: dict, height: int, width: int) -> dict:
    """Bilinear resize of one image struct (PIL, uint8 path)."""
    from PIL import Image
    arr = imageStructToArray(struct)
    if arr.dtype != np.uint8:
        raise ValueError("resizeImage supports uint8 structs")
    img = Image.fromarray(arr.squeeze() if arr.shape[2] == 1 else arr)
    resized = np.asarray(img.resize((width, height), Image.BILINEAR),
                         dtype=np.uint8)
    if resized.ndim == 2:
        resized = resized[:, :, None]
    return imageArrayToStruct(resized, origin=struct.get("origin", ""))


def createResizeImageUDF(height: int, width: int):
    """Row-wise resize fn for ``DataFrame.withColumn`` — the reference's
    ``createResizeImageUDF(size)`` surface: register once, apply to any
    image-struct column. (Batch hot paths resize inside the packer /
    ``imageColumnToNHWC`` instead.)"""

    def resize(struct: dict) -> dict:
        return resizeImage(struct, height, width)

    return resize


def resizeImageBatchNHWC(batch: np.ndarray, height: int, width: int,
                         device: bool = False) -> np.ndarray:
    """Vectorized NHWC resize on device-bound data.

    Uses ``jax.image.resize`` (XLA gather-based bilinear) so resize fuses into
    the same compiled program as preprocessing — the reference instead resized
    row-at-a-time in a Spark UDF (SURVEY.md §3.1 step 2).

    The resize is jitted and shape-cached (``runtime.jit_resize_nhwc``):
    one compilation per (input shape, target), where the old bare
    ``jax.image.resize`` call re-traced its gather chain on EVERY call.
    ``device=True`` returns the device array as-is — callers feeding
    ``jax.device_put``/another jitted program skip the forced
    ``np.asarray`` host sync entirely.
    """
    from ..core.runtime import jit_resize_nhwc
    out = jit_resize_nhwc(height, width)(batch)
    return out if device else np.asarray(out)


def _narrowing_safe(img: np.ndarray, out_dtype) -> np.ndarray:
    """Guard float pixels entering a uint8 batch: numpy's unsafe cast would
    truncate-and-wrap silently (0.9→0, -1→255, 300→44); round+clip instead.
    Requesting uint8 output for float-mode images is still lossy — callers
    that must preserve float data should request dtype=float32."""
    if (np.dtype(out_dtype) == np.uint8
            and np.issubdtype(img.dtype, np.floating)):
        return np.clip(np.round(img), 0, 255)
    return img


def structsToNHWC(structs: Sequence[dict], height: int | None = None,
                  width: int | None = None, dtype=np.float32,
                  channelOrder: str = "RGB") -> np.ndarray:
    """Column of image structs → one contiguous NHWC batch array.

    Structs store BGR(A) at rest; ``channelOrder="RGB"`` (default) flips to
    the model convention here, at the single batch-assembly point. Mixed sizes
    are resized (PIL) to (height, width); if not given, all images must share
    one shape.
    """
    if not structs:
        raise ValueError("empty image column")
    first = structs[0]
    h = height if height is not None else first["height"]
    w = width if width is not None else first["width"]
    c = first["nChannels"]
    flip = channelOrder.upper() == "RGB" and c >= 3
    if all(s["nChannels"] == c for s in structs):
        packed = _native_pack_or_none(
            lambda: [s["data"] for s in structs],
            [s["height"] for s in structs], [s["width"] for s in structs],
            [s["mode"] for s in structs], c, h, w, flip, dtype)
        if packed is not None:
            return packed
    out = np.empty((len(structs), h, w, c), dtype=dtype)
    for i, s in enumerate(structs):
        if s["nChannels"] != c:
            raise ValueError(f"Row {i}: channel mismatch {s['nChannels']} != {c}")
        if s["height"] != h or s["width"] != w:
            s = resizeImage(s, h, w)
        arr = imageStructToArray(s)
        out[i] = _narrowing_safe(_swapRB(arr) if flip else arr, out.dtype)
    return out


def imageColumnToNHWC(column: pa.Array, height: int | None = None,
                      width: int | None = None, dtype=np.float32,
                      channelOrder: str = "RGB") -> np.ndarray:
    """Arrow struct column → NHWC batch, reading the struct's child arrays
    directly (no per-row Python dict materialization on this hot boundary).

    Uniform-size rows are filled via zero-copy ``np.frombuffer`` views of the
    Arrow binary buffers; only rows needing a resize detour through PIL.
    """
    if isinstance(column, pa.ChunkedArray):
        column = column.combine_chunks()
    n = len(column)
    if n == 0:
        raise ValueError("empty image column")
    heights = column.field("height").to_numpy(zero_copy_only=False)
    widths = column.field("width").to_numpy(zero_copy_only=False)
    chans = column.field("nChannels").to_numpy(zero_copy_only=False)
    modes = column.field("mode").to_numpy(zero_copy_only=False)
    data = column.field("data")
    h = int(height) if height is not None else int(heights[0])
    w = int(width) if width is not None else int(widths[0])
    c = int(chans[0])
    if not (chans == c).all():
        raise ValueError(f"Mixed channel counts in image column: "
                         f"{sorted(set(chans.tolist()))}")
    flip = channelOrder.upper() == "RGB" and c >= 3
    if _pack_gate(modes, dtype):
        from .. import native
        packed = _arrow_ptr_pack_or_none(data, heights, widths, c, h, w,
                                         flip, dtype)
        if packed is None:  # exotic layout — per-row buffer path
            packed = native.pack_images(
                [data[i].as_buffer() for i in range(n)], heights, widths,
                c, h, w, flip_bgr=flip, dtype=dtype)
        if packed is not None:
            return packed
    out = np.empty((n, h, w, c), dtype=dtype)
    for i in range(n):
        src_dtype = ocvTypeByMode(int(modes[i])).dtype
        view = np.frombuffer(data[i].as_buffer(), dtype=src_dtype)
        if heights[i] == h and widths[i] == w:
            img = view.reshape(h, w, c)
        else:
            struct = {"height": int(heights[i]), "width": int(widths[i]),
                      "nChannels": c, "mode": int(modes[i]),
                      "data": view.tobytes()}
            img = imageStructToArray(resizeImage(struct, h, w))
        out[i] = _narrowing_safe(_swapRB(img) if flip else img, out.dtype)
    return out


def imageColumnUniformSize(column: pa.Array) -> tuple | None:
    """``(height, width, nChannels, mode)`` when EVERY row of the
    image-struct column stores the same values and no row is null — the
    METADATA-ONLY precondition of :func:`imageColumnNHWCView` (int-field
    reads, no buffer-layout inspection, no pixel work). Callers use it to
    decide a feed policy for a chunk without decoding it (the wire-shape
    cap in ``XlaImageTransformer``)."""
    if isinstance(column, pa.ChunkedArray):
        column = column.combine_chunks()
    n = len(column)
    if n == 0 or column.null_count:
        return None
    try:
        heights = column.field("height").to_numpy(zero_copy_only=False)
        widths = column.field("width").to_numpy(zero_copy_only=False)
        chans = column.field("nChannels").to_numpy(zero_copy_only=False)
        modes = column.field("mode").to_numpy(zero_copy_only=False)
    except (KeyError, pa.ArrowInvalid):
        return None
    h, w, c, mode = (int(heights[0]), int(widths[0]), int(chans[0]),
                     int(modes[0]))
    if not ((heights == h).all() and (widths == w).all()
            and (chans == c).all() and (modes == mode).all()):
        return None
    return h, w, c, mode


def imageColumnNHWCView(column: pa.Array,
                        uniform: tuple | None = None) -> np.ndarray | None:
    """ZERO-COPY NHWC view over a uniform image-struct column.

    When every row stores the same (height, width, nChannels, mode) and
    the binary child's rows sit back-to-back (no nulls, uniform lengths —
    the layout every writer here produces), the Arrow values buffer IS an
    NHWC batch: one ``np.frombuffer`` reshape, no per-row work, no copy.
    Returns the **storage-dtype, at-rest BGR(A)** view (read-only — it
    aliases the immutable Arrow buffer), or ``None`` whenever any layout
    precondition fails, in which case the caller takes a packing path.

    ``uniform``: a precomputed :func:`imageColumnUniformSize` result for
    this exact column — skips the metadata re-scan on the hot path (the
    wire-shape budget in ``XlaImageTransformer`` already ran it).

    This is the host-ingest fast path (ISSUE 7): decode cost for a
    uniform uint8 column drops to ~zero, and the view flows straight into
    ``device_put`` with channel-flip/cast/resize fused into the jitted
    program (``BatchRunner(preprocess=...)``).
    """
    if isinstance(column, pa.ChunkedArray):
        column = column.combine_chunks()
    meta = uniform if uniform is not None else imageColumnUniformSize(column)
    if meta is None:
        return None
    h, w, c, mode = meta
    n = len(column)
    try:
        data = column.field("data")
    except (KeyError, pa.ArrowInvalid):
        return None
    if mode not in _OCV_BY_ORD:
        return None  # let the packing path raise its informative error
    dt = np.dtype(_OCV_BY_ORD[mode].dtype)
    if pa.types.is_binary(data.type):
        off_dtype = np.dtype(np.int32)
    elif pa.types.is_large_binary(data.type):
        off_dtype = np.dtype(np.int64)
    else:
        return None
    if data.null_count:
        return None
    bufs = data.buffers()
    offsets = np.frombuffer(
        bufs[1], dtype=off_dtype, count=n + 1,
        offset=data.offset * off_dtype.itemsize)
    row_bytes = h * w * c * dt.itemsize
    if not (np.diff(offsets) == row_bytes).all():
        return None
    view = np.frombuffer(
        bufs[2], dtype=dt, count=n * h * w * c,
        offset=int(offsets[0])).reshape(n, h, w, c)
    view.flags.writeable = False  # aliases Arrow memory — never mutate
    return view


def imageColumnFeed(column: pa.Array, height: int, width: int,
                    dtype=np.float32, channelOrder: str = "RGB",
                    fused: bool = True, native_ok: bool = True,
                    uniform: tuple | None = None) -> np.ndarray:
    """Feed-side decode policy for the streaming scorer (ISSUE 7).

    ``fused=True`` (the ``SPARKDL_FUSED_PREPROCESS`` default) pairs with a
    jitted preprocess prologue that does flip/cast/resize on device, so
    the host ships the cheapest batch that policy allows:

    - a uniform column whose stored size is ≤ the target's pixel count
      returns the ZERO-COPY storage-dtype **BGR** view at its native size
      (fewer or equal bytes over the wire than a target-size batch, zero
      host pixel math; the device upsamples);
    - anything else (mixed sizes, nulls, stored > target — downsampling
      on device would INFLATE wire bytes)
      packs to the target size in ``dtype``, still **BGR** — the prologue
      owns the flip either way, so every chunk of a stream agrees.

    ``fused=False`` is the legacy host path: pack to target size in
    ``dtype`` with ``channelOrder`` applied on the host.

    Single-row columns always pack: the quarantine row-fallback re-decodes
    a failed chunk one row at a time, and a 1-row slice is trivially
    "uniform" — shipping it at native size would make every mixed-size
    row's shape deviate from the fallback's modal shape and dead-letter
    valid rows (the chunk view path cannot raise, so a fallback only ever
    follows a failed PACK — packing the rows matches it). Costs at most
    one extra wire shape for a legitimate 1-row tail chunk.

    ``native_ok=False`` forces the fused PACK path even for a shippable
    uniform column — the caller's wire-shape budget said no (every
    distinct native size is one XLA compilation; ``XlaImageTransformer``
    caps how many a stage may introduce, ``SPARKDL_MAX_WIRE_SHAPES``).
    ``uniform``: precomputed metadata for this column, forwarded to
    :func:`imageColumnNHWCView` so the uniform-size scan runs once.
    """
    if isinstance(column, pa.ChunkedArray):
        column = column.combine_chunks()
    if fused:
        if native_ok and len(column) > 1:
            view = imageColumnNHWCView(column, uniform=uniform)
            if view is not None and \
                    view.shape[1] * view.shape[2] <= int(height) * int(width):
                return view
        return imageColumnToNHWC(column, height, width, dtype=dtype,
                                 channelOrder="BGR")
    return imageColumnToNHWC(column, height, width, dtype=dtype,
                             channelOrder=channelOrder)


def _pack_gate(modes, dtype) -> bool:
    """THE native-packer eligibility gate (one copy: both the struct-list
    and Arrow column paths consult it): supported output dtype, not
    disabled by env, all rows uint8-moded. NB: the pure-python fallback
    resizes through uint8 (PIL), so resized values can differ from the
    native float path by <1 level — native.py logs once when the library
    is unavailable."""
    if (np.dtype(dtype) not in (np.dtype(np.float32), np.dtype(np.uint8))
            or os.environ.get("SPARKDL_TPU_NATIVE", "1") == "0"
            or not all(ocvTypeByMode(int(m)).dtype == "uint8"
                       for m in modes)):
        return False
    from .. import native
    return native.available()


def _native_pack_or_none(buffers_fn, heights, widths, modes, c, h, w, flip,
                         dtype):
    """Struct-list entry to the native packer (C++: threaded resize +
    channel flip + u8→f32/u8 in one pass; the TensorFrames-JNI-equivalent
    role, SURVEY.md §2.3). None ⇒ caller takes the pure-python path.
    ``buffers_fn`` defers per-row buffer materialization until the gate
    has passed."""
    if not _pack_gate(modes, dtype):
        return None
    from .. import native
    return native.pack_images(buffers_fn(), heights, widths, c, h, w,
                              flip_bgr=flip, dtype=dtype)


def _arrow_ptr_pack_or_none(data: pa.Array, heights, widths, c, h, w,
                            flip, dtype):
    """Zero-copy Arrow fast path: source addresses come straight from the
    binary child's values buffer + offsets — no per-row buffer objects
    and no per-row ctypes casts, which cost ~30% of wall time on the
    per-row path at 299x299. Caller has already passed ``_pack_gate``;
    this adds only LAYOUT checks, returning None for layouts it doesn't
    cover (nulls, non-binary storage); size mismatches raise, matching
    pack_images' contract."""
    from .. import native

    if pa.types.is_binary(data.type):
        off_dtype = np.dtype(np.int32)
    elif pa.types.is_large_binary(data.type):
        off_dtype = np.dtype(np.int64)
    else:
        return None
    if data.null_count:
        return None
    bufs = data.buffers()
    offsets = np.frombuffer(
        bufs[1], dtype=off_dtype, count=len(data) + 1,
        offset=data.offset * off_dtype.itemsize).astype(np.int64)
    lens = np.diff(offsets)
    expected = (np.asarray(heights, np.int64)
                * np.asarray(widths, np.int64) * c)
    if not (lens == expected).all():
        i = int(np.argmax(lens != expected))
        raise ValueError(
            f"Image {i}: buffer has {lens[i]} bytes, expected "
            f"{heights[i]}x{widths[i]}x{c}")
    ptrs = np.uint64(bufs[2].address) + offsets[:-1].astype(np.uint64)
    return native.pack_images_ptrs(ptrs, heights, widths, c, h, w,
                                   flip_bgr=flip, dtype=dtype)


def nhwcToImageColumn(batch: np.ndarray,
                      origins: Sequence[str] | None = None,
                      channelOrder: str = "RGB",
                      copy: bool = True) -> pa.StructArray:
    """Vectorized NHWC batch → image struct COLUMN (the write-side twin
    of :func:`imageColumnToNHWC`): the whole batch becomes one contiguous
    Arrow values buffer with arithmetic offsets — no per-row dict/bytes
    objects, whose GIL-bound assembly caps out around 4k rows/s however
    many host cores exist. Same conventions as :func:`nhwcToStructs`:
    input is RGB by default, stored structs are BGR at rest.

    ``copy=False`` skips the defensive copy when no channel swap is
    needed, zero-copy-wrapping the CALLER'S buffer — only for callers
    that never mutate ``batch`` afterwards (mutating it would silently
    corrupt the column's supposedly immutable data)."""
    src = np.asarray(batch)
    if src.ndim != 4:
        raise ValueError(f"Expected NHWC batch, got shape {src.shape}")
    n, h, w, c = src.shape
    key = (str(src.dtype), c)
    if key not in _OCV_BY_KEY:
        raise ValueError(f"Unsupported dtype/channels {key}; supported: "
                         f"{sorted(_OCV_BY_KEY)}")
    t = _OCV_BY_KEY[key]
    if channelOrder.upper() == "RGB" and c >= 3:
        batch = np.ascontiguousarray(_swapRB(src))  # new owned array
    else:
        batch = np.ascontiguousarray(src)
        if copy and batch is src:
            # ascontiguousarray was a no-op: without this copy the Arrow
            # column would alias the caller's mutable buffer
            batch = batch.copy()
    row_nbytes = h * w * c * batch.itemsize
    total = n * row_nbytes
    if total > 2**31 - 1:
        raise ValueError(
            f"batch is {total} bytes — exceeds the int32 offsets of the "
            f"image column's binary storage; convert in chunks")
    offsets = (np.arange(n + 1, dtype=np.int32) * row_nbytes)
    data = pa.Array.from_buffers(
        pa.binary(), n,
        [None, pa.py_buffer(offsets), pa.py_buffer(batch)], null_count=0)
    const = lambda v: pa.array(np.full(n, v, dtype=np.int32))
    origin_arr = pa.array(
        [""] * n if origins is None else list(origins), type=pa.string())
    if len(origin_arr) != n:
        raise ValueError(f"{len(origin_arr)} origins for {n} rows")
    return pa.StructArray.from_arrays(
        [origin_arr, const(h), const(w), const(c), const(t.ord), data],
        fields=list(imageSchema))


def nhwcToStructs(batch: np.ndarray, origins: Sequence[str] | None = None,
                  channelOrder: str = "RGB") -> list[dict]:
    """NHWC batch → image structs. Input is RGB by default (the model
    convention); stored structs are BGR per the at-rest convention."""
    origins = origins or [""] * len(batch)
    flip = channelOrder.upper() == "RGB" and batch.shape[-1] >= 3
    return [imageArrayToStruct(
        np.ascontiguousarray(_swapRB(np.asarray(img))) if flip
        else np.asarray(img), origin=o)
        for img, o in zip(batch, origins)]


# ---------------------------------------------------------------------------
# Readers (reference: readImages / readImagesWithCustomFn)
# ---------------------------------------------------------------------------

_IMAGE_EXTENSIONS = {".jpg", ".jpeg", ".png", ".gif", ".bmp", ".webp"}

_POOL = None
_POOL_LOCK = __import__("threading").Lock()


def _decode_pool():
    """ONE process-wide decode executor shared by every reader DataFrame —
    a per-reader pool would pin its threads for the reader's lifetime and
    accumulate across many readImages calls in a long-lived driver."""
    global _POOL
    with _POOL_LOCK:
        if _POOL is None:
            from concurrent.futures import ThreadPoolExecutor
            _POOL = ThreadPoolExecutor(
                max_workers=min(os.cpu_count() or 1, 16),
                thread_name_prefix="sparkdl-decode")
        return _POOL


def _list_image_files(path: str, recursive: bool = True) -> list[str]:
    if os.path.isfile(path):
        return [path]
    files = []
    for root, dirs, names in os.walk(path):
        dirs.sort()  # deterministic walk order — seeded sampleRatio
        # draws the same files on every filesystem
        for n in sorted(names):
            if os.path.splitext(n)[1].lower() in _IMAGE_EXTENSIONS:
                files.append(os.path.join(root, n))
        if not recursive:
            break
    return files


def readImages(path: str, numPartitions: int = 1,
               dropImageFailures: bool = True, sampleRatio: float = 1.0,
               seed: int = 42):
    """Directory/file of images → DataFrame[image: imageSchema].

    Reference behavior: ``readImages`` returns a DataFrame with an ``image``
    struct column, silently dropping undecodable files when asked;
    ``sampleRatio`` takes a seeded random fraction of the file listing
    (the reference's large-directory sampling knob).

    LAZY: only file *URIs* are enumerated here; decode runs inside a
    row-wise DataFrame op at materialization time, so scoring N images
    through a downstream transformer holds O(batchSize) decoded pixels in
    host memory, never the whole dataset (the BASELINE "batch-scores 1M
    images" north star; round-1 verdict item 4).
    """
    # decodeImage (PIL) is thread-safe → pooled decode (decodeWorkers=0).
    return readImagesWithCustomFn(path, decode_fn=decodeImage,
                                  numPartitions=numPartitions,
                                  dropImageFailures=dropImageFailures,
                                  decodeWorkers=0,
                                  sampleRatio=sampleRatio, seed=seed)


def readImagesWithCustomFn(path: str, decode_fn: Callable[[bytes, str], dict | None],
                           numPartitions: int = 1,
                           dropImageFailures: bool = True,
                           decodeWorkers: int = 1,
                           sampleRatio: float = 1.0, seed: int = 42):
    """``decodeWorkers``: 1 (default) keeps the historical SEQUENTIAL
    contract — a custom ``decode_fn`` may use shared mutable state. Pass 0
    (auto: min(cpu_count, 16)) or N>1 to fan decode over a thread pool;
    ``decode_fn`` must then be thread-safe (the built-in PIL decoder is —
    ``readImages`` uses the pooled path)."""
    from ..core.frame import DataFrame
    if not 0.0 < sampleRatio <= 1.0:
        raise ValueError(f"sampleRatio must be in (0, 1], got {sampleRatio}")
    files = _list_image_files(path)
    if not files:
        raise FileNotFoundError(f"No image files under {path!r}")
    if sampleRatio < 1.0:
        # seeded per-file Bernoulli over the sorted listing — stable for a
        # fixed seed regardless of numPartitions
        rng = np.random.RandomState(seed)
        keep = rng.random_sample(len(files)) < sampleRatio
        files = [f for f, k in zip(files, keep) if k]
        if not files:
            raise ValueError(
                f"sampleRatio={sampleRatio} over {int(keep.size)} files "
                f"sampled zero rows (seed={seed}); raise the ratio or "
                f"change the seed")
    workers = (min(os.cpu_count() or 1, 16) if decodeWorkers == 0
               else max(1, decodeWorkers))

    # Closure counters: the single-process data plane applies ops
    # sequentially, so once every listed file has been seen with zero
    # successful decodes we can reproduce the eager reader's loud
    # "all files failed" error instead of silently yielding 0 rows.
    progress = {"seen": 0, "ok": 0}

    def read_one(uri: str):
        """Runs on a pool thread (file IO + PIL decode release the GIL);
        OSError is carried back as a value so ordering/error policy stays
        on the consumer side."""
        try:
            with open(uri, "rb") as fh:
                return decode_fn(fh.read(), uri)
        except OSError as e:
            return e

    def decode_wave(uris):
        """Decode URIs in bounded waves so dropImageFailures=False still
        fails fast — a bad first file can't trigger the decode of a whole
        512-row batch before the error surfaces.

        decodeWorkers=0 (auto, the readImages default — thread-safe PIL
        decode) rides the process-wide shared executor. An EXPLICIT
        decodeWorkers=N gets a dedicated pool of exactly N threads for
        this batch (the caller's concurrency contract for decode fns that
        are only N-thread-safe or memory-budgeted), shut down after.
        """
        if workers == 1 or len(uris) <= 1:
            for u in uris:
                yield u, read_one(u)
            return
        if decodeWorkers == 0:
            pool = _decode_pool()  # shared, min(cpu_count, 16) threads
            wave = 2 * (os.cpu_count() or 1)
            for start in range(0, len(uris), wave):
                chunk = uris[start:start + wave]
                yield from zip(chunk, pool.map(read_one, chunk))
            return
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(max_workers=workers) as pool:
            wave = 2 * workers
            for start in range(0, len(uris), wave):
                chunk = uris[start:start + wave]
                yield from zip(chunk, pool.map(read_one, chunk))

    def decode_op(batch: pa.RecordBatch) -> pa.RecordBatch:
        uris = batch.column("_uri").to_pylist()
        structs = []
        for uri, s in decode_wave(uris):
            progress["seen"] += 1
            if isinstance(s, OSError):
                if dropImageFailures:
                    s = None
                else:
                    # dropImageFailures=False exists to surface problems:
                    # an unreadable file raises, it does not become a
                    # placeholder row.
                    raise s
            if s is None:
                if dropImageFailures:
                    continue
                s = {"origin": uri, "height": -1, "width": -1,
                     "nChannels": -1, "mode": -1, "data": b""}
            else:
                progress["ok"] += 1
            structs.append(s)
        if (dropImageFailures and progress["seen"] >= len(files)
                and progress["ok"] == 0):
            raise ValueError(f"All {len(files)} image files failed to decode")
        return pa.RecordBatch.from_arrays(
            [pa.array(structs, type=imageSchema)], names=["image"])

    # Row-wise: each output row depends only on its own input row, so the
    # streaming materializer may apply it per sub-partition chunk.
    decode_op._row_wise = True
    decode_op._changes_length = dropImageFailures

    uris = DataFrame.fromPydict({"_uri": files},
                                numPartitions=numPartitions)
    return uris.mapBatches(decode_op)
