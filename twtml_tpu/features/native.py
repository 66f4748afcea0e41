"""ctypes bridge to the C++ fast featurizer (native/fasthash.cpp).

Builds the shared library on first use (g++ is in the image; no network or
pybind11 required), loads it via ctypes, and exposes ``fasthash_batch``
filling padded numpy buffers in place. The library is only ever loaded when
the stamp beside it says it was built ON THIS HOST from the CURRENT sources
with the CURRENT flags (``-march=native`` code from another CPU dies with
SIGILL; an mtime survives a copy, a stamp of what was compiled does not).
Where no compiler is available the apps degrade — at WARNING — to the
Python path: features/hashing.py stays the semantic ground truth and the
parity test asserts the two implementations agree bigram-for-bigram.
Measurement entry points (``chip_smoke.py``, ``benchmark/``) call
``require_live()`` and fail instead of timing the fallback.
"""

from __future__ import annotations

import contextlib
import ctypes
import hashlib
import json
import os
import platform
import subprocess
import threading

import numpy as np

from ..utils import get_logger

log = get_logger("features.native")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRCS = [
    os.path.join(_REPO_ROOT, "native", "fasthash.cpp"),
    os.path.join(_REPO_ROOT, "native", "tweetjson.cpp"),
    os.path.join(_REPO_ROOT, "native", "wirecodec.cpp"),
    os.path.join(_REPO_ROOT, "native", "wireassemble.cpp"),
    os.path.join(_REPO_ROOT, "native", "featurize.cpp"),
]
# TWTML_NATIVE_LIB: alternate build/load path for the shared library. The
# sanitizer harness (tools/native_sanity.py) builds an ASan/UBSan-
# instrumented copy WITHOUT clobbering the production .so next to the
# sources (a sanitized library needs its runtime preloaded — loading it
# from a normal run would fail).
_LIB = os.environ.get("TWTML_NATIVE_LIB", "") or os.path.join(
    _REPO_ROOT, "native", "libfasthash.so"
)


def _build_flags() -> list[str]:
    """Compile flags: full warnings always (the C parity fast paths get
    the same scrutiny as the Python side); TWTML_NATIVE_SANITIZE adds
    instrumented-build flags — comma-separated subset of {asan, ubsan},
    e.g. ``TWTML_NATIVE_SANITIZE=asan,ubsan`` — at -O1 with frame
    pointers so reports carry usable stacks."""
    flags = ["-O3", "-march=native", "-shared", "-fPIC", "-pthread",
             "-Wall", "-Wextra"]
    san = os.environ.get("TWTML_NATIVE_SANITIZE", "")
    if san:
        modes = {m.strip() for m in san.split(",") if m.strip()}
        unknown = modes - {"asan", "ubsan"}
        if unknown:
            log.warning(
                "TWTML_NATIVE_SANITIZE=%s: unknown mode(s) %s ignored "
                "(known: asan, ubsan)", san, ",".join(sorted(unknown)),
            )
            modes -= unknown
        sanitizers = [s for m, s in (("asan", "address"),
                                     ("ubsan", "undefined")) if m in modes]
        if sanitizers:
            flags = ["-O1", "-g", "-fno-omit-frame-pointer",
                     f"-fsanitize={','.join(sanitizers)}",
                     "-march=native", "-shared", "-fPIC", "-pthread",
                     "-Wall", "-Wextra", "-Werror"]
    return flags

# the C data-loader's per-row text bound (kMaxTextUnits, native/tweetjson.cpp):
# a retweeted status whose text/full_text exceeds this many UTF-16 units makes
# the line a counted bad line in BOTH block paths (C and Python fallback)
MAX_TEXT_UNITS = 4096


def _sources_ok() -> bool:
    return all(os.path.exists(s) for s in _SRCS)


def _host_id() -> str:
    """What ``-march=native`` resolves against: the machine type plus the
    CPU model and ISA flag set the kernel reports."""
    lines = []
    try:
        with open("/proc/cpuinfo", encoding="utf-8") as fh:
            for line in fh:
                if line.startswith(("model name", "flags", "Features")):
                    lines.append(line.strip())
                elif not line.strip() and lines:
                    break  # first processor block only
    except OSError:
        lines = [platform.processor(), platform.node()]
    return hashlib.sha256(
        "\n".join([platform.machine(), *lines]).encode()
    ).hexdigest()


def expected_stamp() -> dict:
    """The identity a loadable library must carry: a hash of the tracked
    sources, the compile flags, and the host it was compiled on."""
    h = hashlib.sha256()
    for src in _SRCS:
        with open(src, "rb") as fh:
            h.update(os.path.basename(src).encode() + b"\0" + fh.read())
    return {
        "sources": h.hexdigest(),
        "flags": _build_flags(),
        "host": _host_id(),
    }


def read_stamp() -> "dict | None":
    """The stamp beside the library, or None (no library / never stamped /
    unreadable — all of which mean "do not load it")."""
    if not os.path.exists(_LIB):
        return None
    try:
        with open(_LIB + ".stamp", encoding="utf-8") as fh:
            return json.load(fh)
    except (OSError, ValueError):
        return None

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False
# set when the loaded library predates the wire emitter (stale .so whose
# OLD symbol set still works): parse_tweet_block_wire() then returns None
# and block sources degrade LOUDLY to the ParsedBlock path — one warning +
# a registry counter, never a ctypes AttributeError mid-stream
_wire_missing = False
# same degrade seam for the digram wire-codec encoder (r15): a stale
# library missing ``digram_encode`` only flags this, and the codec falls
# back to the byte-identical numpy encoder (features/wirecodec.encode_np)
_codec_missing = False
# and for the fused wire assembler (r17): a stale library missing
# ``wire_assemble`` only flags this — one warning + the
# ``native.assemble_degraded`` counter — and every pack falls back to the
# byte-identical numpy pipeline (features/batch.py, the ground truth)
_assemble_missing = False
# and for the one-pass featurize emitter (r18): a stale library missing
# ``featurize_wire`` only flags this — one warning + the
# ``native.featurize_degraded`` counter — and the featurizer keeps
# running on the byte-identical Python/numpy path (the ground truth)
_featurize_missing = False


def _build(stamp: dict) -> bool:
    # build to a temp path and os.replace: dlopen caches by inode, so a
    # rebuild in place would hand a retrying loader the same stale image —
    # the replace gives the retry a fresh inode. The stamp goes first and
    # comes back last, so a half-finished build is never taken for a
    # stamped one.
    # Temp names carry the pid: several processes of one run may find the
    # library missing at once, and each must compile into its own file.
    tmp = f"{_LIB}.{os.getpid()}.tmp"
    try:
        with contextlib.suppress(FileNotFoundError):
            os.remove(_LIB + ".stamp")
        subprocess.run(
            ["g++", *stamp["flags"], "-o", tmp, *_SRCS],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, _LIB)
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(stamp, fh)
        os.replace(tmp, _LIB + ".stamp")
        log.info("native library built on this host from native/*.cpp")
        return True
    except (OSError, subprocess.SubprocessError) as exc:
        log.warning("native featurizer build failed (%s); using python path", exc)
        with contextlib.suppress(OSError):
            os.remove(tmp)
        return False


def get_lib() -> ctypes.CDLL | None:
    """Load (building if needed) the native library; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not _sources_ok():
            log.warning(
                "native/*.cpp sources missing: no library can be built or "
                "trusted; using python path"
            )
            return None
        stamp = expected_stamp()
        if read_stamp() != stamp and not _build(stamp):
            return None
        try:
            lib = _load(_LIB)
        except AttributeError:
            # the CURRENT sources lack a symbol the binder expects (a
            # development-time mismatch; a rebuild would give the same
            # image): keep the symbols it has, flag the rest loudly
            lib = _try_degraded_load()
            if lib is None:
                return None
        except OSError as exc:
            log.warning("native featurizer load failed (%s)", exc)
            return None
        _lib = lib
        return _lib


def _try_degraded_load() -> ctypes.CDLL | None:
    """Last-resort load of a stale library: every pre-wire symbol must
    bind (those AttributeErrors stay fatal — the lib is unusably old), but
    a missing wire emitter / codec encoder only flags ``_wire_missing`` /
    ``_codec_missing`` so block sources fall back to the ParsedBlock path
    (and the codec to its numpy encoder) instead of dying mid-stream."""
    try:
        return _load(_LIB, strict=False)
    except (OSError, AttributeError) as exc:
        log.warning("native featurizer is stale and could not be rebuilt "
                    "or loaded (%s); using python path", exc)
        return None


def _load(path: str, strict: bool = True) -> ctypes.CDLL:
    """dlopen + bind every exported symbol; AttributeError = stale library.
    ``strict=False`` tolerates the post-r6 additions — the wire emitter
    and the codec encoder — by flagging ``_wire_missing`` /
    ``_codec_missing`` instead of raising (see get_lib)."""
    lib = ctypes.CDLL(path)
    lib.fasthash_batch.restype = ctypes.c_int32
    lib.fasthash_batch.argtypes = [
        ctypes.POINTER(ctypes.c_uint16),  # units
        ctypes.POINTER(ctypes.c_int64),  # offsets
        ctypes.c_int32,  # batch
        ctypes.c_int32,  # num_features
        ctypes.c_int32,  # l_max
        ctypes.POINTER(ctypes.c_int32),  # out_idx
        ctypes.POINTER(ctypes.c_float),  # out_val
        ctypes.POINTER(ctypes.c_int32),  # out_ntok
        ctypes.c_int32,  # n_threads (<=0 = auto)
    ]
    lib.pad_units_batch.restype = ctypes.c_int32
    lib.pad_units_batch.argtypes = [
        ctypes.POINTER(ctypes.c_uint16),  # units
        ctypes.POINTER(ctypes.c_int64),  # offsets
        ctypes.c_int32,  # batch
        ctypes.c_int32,  # padded_rows
        ctypes.c_int32,  # l_max
        ctypes.c_int32,  # ascii_lower
        ctypes.POINTER(ctypes.c_uint16),  # out_units
        ctypes.POINTER(ctypes.c_int32),  # out_len
    ]
    lib.pad_units_batch_u8.restype = ctypes.c_int32
    lib.pad_units_batch_u8.argtypes = [
        ctypes.POINTER(ctypes.c_uint16),  # units
        ctypes.POINTER(ctypes.c_int64),  # offsets
        ctypes.c_int32,  # batch
        ctypes.c_int32,  # padded_rows
        ctypes.c_int32,  # l_max
        ctypes.c_int32,  # ascii_lower
        ctypes.POINTER(ctypes.c_uint8),  # out_units
        ctypes.POINTER(ctypes.c_int32),  # out_len
    ]
    lib.lexicon_score_batch.restype = None
    lib.lexicon_score_batch.argtypes = [
        ctypes.c_void_p,  # units (uint16, or the narrow wire's uint8)
        ctypes.c_int32,  # unit_bytes
        ctypes.POINTER(ctypes.c_int64),  # offsets
        ctypes.c_int32,  # batch
        ctypes.POINTER(ctypes.c_uint16),  # pos_words
        ctypes.POINTER(ctypes.c_int64),  # pos_off
        ctypes.POINTER(ctypes.c_int32),  # pos_hash
        ctypes.c_int32,  # n_pos
        ctypes.POINTER(ctypes.c_uint16),  # neg_words
        ctypes.POINTER(ctypes.c_int64),  # neg_off
        ctypes.POINTER(ctypes.c_int32),  # neg_hash
        ctypes.c_int32,  # n_neg
        ctypes.POINTER(ctypes.c_int32),  # out_score
    ]
    lib.parse_tweet_block.restype = ctypes.c_int64
    lib.parse_tweet_block.argtypes = [
        ctypes.c_char_p,  # buf
        ctypes.c_int64,  # len
        ctypes.c_int64,  # begin
        ctypes.c_int64,  # end
        ctypes.c_int64,  # cap_rows
        ctypes.c_int64,  # cap_units
        ctypes.POINTER(ctypes.c_int64),  # out_numeric [rows,5]
        ctypes.POINTER(ctypes.c_uint16),  # out_units
        ctypes.POINTER(ctypes.c_int64),  # out_offsets [rows+1]
        ctypes.POINTER(ctypes.c_uint8),  # out_ascii [rows]
        ctypes.POINTER(ctypes.c_int64),  # consumed
        ctypes.POINTER(ctypes.c_int64),  # bad_lines
    ]
    _bind_wire(lib, strict)
    _bind_codec(lib, strict)
    _bind_assemble(lib, strict)
    _bind_featurize(lib, strict)
    return lib


def _bind_wire(lib: ctypes.CDLL, strict: bool) -> None:
    """Bind the zero-copy wire emitter. A library missing it is stale;
    strict loads raise (so get_lib's rebuild kicks in), degraded loads flag
    ``_wire_missing`` ONCE — warning + ``native.wire_degraded`` counter —
    and the block sources keep running on the ParsedBlock path."""
    global _wire_missing
    try:
        fn = lib.parse_tweet_block_wire
    except AttributeError:
        if strict:
            raise
        _wire_missing = True
        log.warning(
            "native library is stale: parse_tweet_block_wire missing — "
            "block sources degrade to the ParsedBlock parser (delete "
            "native/libfasthash.so to force a rebuild of the zero-copy "
            "wire path)"
        )
        from ..telemetry import metrics as _metrics

        _metrics.get_registry().counter("native.wire_degraded").inc()
        return
    fn.restype = ctypes.c_int64
    fn.argtypes = [
        ctypes.c_char_p,  # buf
        ctypes.c_int64,  # len
        ctypes.c_int64,  # begin
        ctypes.c_int64,  # end
        ctypes.c_int64,  # cap_rows
        ctypes.c_int64,  # cap_units
        ctypes.POINTER(ctypes.c_int64),  # out_numeric [rows,5]
        ctypes.POINTER(ctypes.c_uint8),  # out_units_u8
        ctypes.POINTER(ctypes.c_uint16),  # out_units_u16
        ctypes.POINTER(ctypes.c_int64),  # out_offsets [rows+1]
        ctypes.POINTER(ctypes.c_uint8),  # out_ascii [rows]
        ctypes.POINTER(ctypes.c_int64),  # consumed
        ctypes.POINTER(ctypes.c_int64),  # bad_lines
        ctypes.POINTER(ctypes.c_int64),  # narrow (out)
        ctypes.POINTER(ctypes.c_int64),  # needs_wide (out)
    ]
    _wire_missing = False


def _bind_codec(lib: ctypes.CDLL, strict: bool) -> None:
    """Bind the digram wire-codec encoder (native/wirecodec.cpp). Same
    degrade contract as ``_bind_wire``: strict loads raise (get_lib
    rebuilds), degraded loads flag ``_codec_missing`` ONCE and the codec
    keeps running on the byte-identical numpy encoder."""
    global _codec_missing
    try:
        fn = lib.digram_encode
    except AttributeError:
        if strict:
            raise
        _codec_missing = True
        log.warning(
            "native library is stale: digram_encode missing — the wire "
            "codec uses the numpy encoder (delete native/libfasthash.so "
            "to force a rebuild of the C fast path)"
        )
        from ..telemetry import metrics as _metrics

        _metrics.get_registry().counter("native.codec_degraded").inc()
        return
    fn.restype = ctypes.c_int64
    fn.argtypes = [
        ctypes.POINTER(ctypes.c_uint8),  # in
        ctypes.c_int64,  # n
        ctypes.POINTER(ctypes.c_uint8),  # lut[65536]
        ctypes.POINTER(ctypes.c_uint8),  # out
        ctypes.c_int64,  # cap
    ]
    _codec_missing = False


def _bind_assemble(lib: ctypes.CDLL, strict: bool) -> None:
    """Bind the fused one-pass wire assembler (native/wireassemble.cpp).
    Same degrade contract as ``_bind_wire``/``_bind_codec``: strict loads
    raise (get_lib rebuilds), degraded loads flag ``_assemble_missing``
    ONCE — warning + ``native.assemble_degraded`` counter — and every
    pack keeps running on the byte-identical numpy pipeline."""
    global _assemble_missing
    try:
        fn = lib.wire_assemble
    except AttributeError:
        if strict:
            raise
        _assemble_missing = True
        log.warning(
            "native library is stale: wire_assemble missing — packs use "
            "the numpy pipeline (delete native/libfasthash.so to force a "
            "rebuild of the fused one-pass assembler)"
        )
        from ..telemetry import metrics as _metrics

        _metrics.get_registry().counter("native.assemble_degraded").inc()
        return
    fn.restype = ctypes.c_int64
    fn.argtypes = [
        ctypes.POINTER(ctypes.c_void_p),  # units ptrs [k]
        ctypes.POINTER(ctypes.c_void_p),  # offsets ptrs [k]
        ctypes.POINTER(ctypes.c_void_p),  # numeric ptrs [k]
        ctypes.POINTER(ctypes.c_void_p),  # label ptrs [k]
        ctypes.POINTER(ctypes.c_void_p),  # mask ptrs [k]
        ctypes.c_int64,  # k
        ctypes.c_int64,  # s
        ctypes.c_int64,  # n_sb
        ctypes.c_int64,  # bl
        ctypes.c_int64,  # unit_size
        ctypes.c_int64,  # narrow_offsets
        ctypes.POINTER(ctypes.c_uint8),  # lut (None = codec off)
        ctypes.c_int64,  # forced codec bucket
        ctypes.POINTER(ctypes.c_uint8),  # scratch
        ctypes.POINTER(ctypes.c_int64),  # enc_lens
        ctypes.POINTER(ctypes.c_uint8),  # out
        ctypes.c_int64,  # cap
        ctypes.POINTER(ctypes.c_int64),  # out enc_bucket
    ]
    _assemble_missing = False


def _bind_featurize(lib: ctypes.CDLL, strict: bool) -> None:
    """Bind the one-pass featurize emitter (native/featurize.cpp). Same
    degrade contract as its siblings: strict loads raise (get_lib
    rebuilds), degraded loads flag ``_featurize_missing`` ONCE — warning
    + ``native.featurize_degraded`` counter — and the featurizer keeps
    running on the byte-identical Python/numpy ground truth."""
    global _featurize_missing
    try:
        fn = lib.featurize_wire
    except AttributeError:
        if strict:
            raise
        _featurize_missing = True
        log.warning(
            "native library is stale: featurize_wire missing — featurize "
            "uses the Python/numpy path (delete native/libfasthash.so to "
            "force a rebuild of the one-pass featurize emitter)"
        )
        from ..telemetry import metrics as _metrics

        _metrics.get_registry().counter("native.featurize_degraded").inc()
        return
    fn.restype = ctypes.c_int64
    # every pointer is c_void_p on purpose: the wrapper passes raw
    # ``arr.ctypes.data`` integers — ``data_as`` casts measured ~7 µs
    # EACH and this entry runs per batch on the featurize hot path
    fn.argtypes = [
        ctypes.c_void_p,  # units
        ctypes.c_int64,  # unit_size
        ctypes.c_void_p,  # offsets [n+1] int64
        ctypes.c_void_p,  # cols_f64 [n,5] or None
        ctypes.c_void_p,  # cols_i64 [n,5] or None
        ctypes.c_void_p,  # col_order [5] int64
        ctypes.c_int64,  # n
        ctypes.c_int64,  # b
        ctypes.c_int64,  # n_bucket
        ctypes.c_int64,  # now_ms
        ctypes.c_int64,  # narrow
        ctypes.c_void_p,  # out_units
        ctypes.c_void_p,  # out_offsets [b+1] int32
        ctypes.c_void_p,  # out_numeric [b,4] f32
        ctypes.c_void_p,  # out_label [b] f32
        ctypes.c_void_p,  # out_mask [b] f32
    ]
    _featurize_missing = False


def featurize_available() -> bool:
    """Whether the one-pass featurize emitter is loadable (library up and
    the symbol present — see _bind_featurize's degrade seam)."""
    return get_lib() is not None and not _featurize_missing


def featurize_wire_raw(*args) -> "int | None":
    """Raw-pointer form of the one-pass featurize entry: ``args`` are
    exactly the C signature's 16 values with every pointer as a plain
    int (or None). The hot caller (features/featurize_native.try_fill)
    computes its five output pointers from the ONE lease base address —
    each numpy ``.ctypes`` access builds an interface object (~2-3 µs)
    and this entry runs per batch. Returns the max row length, or None
    when the library is unavailable, predates the emitter, or refuses
    the input — callers fall back to the Python/numpy ground truth."""
    lib = get_lib()
    if lib is None or _featurize_missing:
        return None
    max_len = lib.featurize_wire(*args)
    if max_len < 0:  # caller sized n_bucket from these offsets; never expected
        return None
    return int(max_len)


def featurize_wire(
    units: np.ndarray,
    offsets: np.ndarray,
    cols: np.ndarray,
    col_order: np.ndarray,
    n: int,
    b: int,
    n_bucket: int,
    now_ms: int,
    narrow: bool,
    out_units: np.ndarray,
    out_offsets: np.ndarray,
    out_numeric: np.ndarray,
    out_label: np.ndarray,
    out_mask: np.ndarray,
) -> "int | None":
    """One C pass from encoded units + numeric columns to the final
    ragged-wire arrays (native/featurize.cpp): flat units (narrow uint8
    under the caller's metadata gate), padded int32 offsets, scaled f32
    numeric/label/mask — all written into the caller's (arena-leased)
    destinations. ``cols`` is float64 [n, 5] (object path) or int64
    [n, 5] (block parser columns); ``col_order`` maps its layout onto
    followers/favourites/friends/created_ms/label. Array-argument
    convenience form of ``featurize_wire_raw`` (same contract)."""
    if cols.dtype == np.float64:
        cols_f64, cols_i64 = cols.ctypes.data, None
    elif cols.dtype == np.int64:
        cols_f64, cols_i64 = None, cols.ctypes.data
    elif n:
        return None
    else:
        cols_f64 = cols_i64 = None
    return featurize_wire_raw(
        units.ctypes.data,
        int(units.dtype.itemsize),
        offsets.ctypes.data,
        cols_f64,
        cols_i64,
        col_order.ctypes.data,
        n,
        b,
        n_bucket,
        int(now_ms),
        1 if narrow else 0,
        out_units.ctypes.data,
        out_offsets.ctypes.data,
        out_numeric.ctypes.data,
        out_label.ctypes.data,
        out_mask.ctypes.data,
    )


def assemble_available() -> bool:
    """Whether the fused wire assembler is loadable (library up and the
    symbol present — see _bind_assemble's degrade seam)."""
    return get_lib() is not None and not _assemble_missing


def _ptr_array(arrays: "list[np.ndarray]"):
    return (ctypes.c_void_p * len(arrays))(
        *[a.ctypes.data for a in arrays]
    )


def wire_assemble(
    units: "list[np.ndarray]",
    offsets: "list[np.ndarray]",
    numeric: "list[np.ndarray]",
    label: "list[np.ndarray]",
    mask: "list[np.ndarray]",
    s: int,
    n_sb: int,
    bl: int,
    narrow: bool,
    lut: "np.ndarray | None",
    forced_bucket: int,
    scratch: "np.ndarray | None",
    enc_lens: "np.ndarray | None",
    out: np.ndarray,
) -> "tuple[int, int] | None":
    """One C pass from K batches' field arrays to the final packed wire
    buffer (native/wireassemble.cpp). Returns (written bytes,
    enc_bucket — 0 = raw units wire), or None when the library is
    unavailable, predates the assembler, or reports an input the caller
    must route through the numpy ground truth (delta overflow, forced
    codec bucket under-coverage — the numpy path raises the canonical
    errors). The caller (features/assemble.py) owns eligibility gating,
    layout construction, and the arena leases for ``scratch``/``out``."""
    lib = get_lib()
    if lib is None or _assemble_missing:
        return None
    k = len(units)
    u8 = ctypes.POINTER(ctypes.c_uint8)
    i64 = ctypes.POINTER(ctypes.c_int64)
    enc_bucket = ctypes.c_int64(0)
    total = lib.wire_assemble(
        _ptr_array(units),
        _ptr_array(offsets),
        _ptr_array(numeric),
        _ptr_array(label),
        _ptr_array(mask),
        k,
        s,
        n_sb,
        bl,
        int(units[0].dtype.itemsize),
        1 if narrow else 0,
        lut.ctypes.data_as(u8) if lut is not None else None,
        int(forced_bucket),
        scratch.ctypes.data_as(u8) if scratch is not None else None,
        enc_lens.ctypes.data_as(i64) if enc_lens is not None else None,
        out.ctypes.data_as(u8),
        int(out.shape[0]),
        ctypes.byref(enc_bucket),
    )
    if total < 0:
        # -2 delta overflow / -3 forced-bucket under-coverage: the numpy
        # path raises the canonical ValueError; -1 capacity means the
        # caller mis-sized the lease — same route, the ground truth can
        # never hit it
        return None
    return int(total), int(enc_bucket.value)


def digram_encode(buf: np.ndarray, lut: np.ndarray) -> "np.ndarray | None":
    """C greedy digram encode of a uint8 buffer (features/wirecodec.py owns
    the dictionary and the numpy ground truth; the two are byte-identical
    by construction and differential-tested). None when the native library
    is unavailable or predates the encoder — callers fall back to
    ``wirecodec.encode_np``. The output can never exceed the input length
    (a pair shrinks, a literal copies), so ``n`` capacity always fits."""
    lib = get_lib()
    if lib is None or _codec_missing:
        return None
    n = int(buf.shape[0])
    out = np.empty((n,), np.uint8)
    m = lib.digram_encode(
        buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        n,
        lut.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        n,
    )
    if m < 0:  # cannot happen with cap = n; be loud if it ever does
        raise RuntimeError("digram_encode overflowed its full-size buffer")
    return out[:m].copy()


def rebind_flags() -> None:
    """Re-evaluate EVERY degrade flag against the real loaded library.
    Test support for the stale-library seam tests: ``_load(path,
    strict=False)`` on an old .so flags every symbol that .so lacks —
    the module-global flags are shared with the production library, so
    a seam test restoring only ITS OWN flag leaves the younger fast
    paths silently degraded for the rest of the process (found by r18's
    lease-accounting tests: the r9 stale test left the r15/r17/r18
    paths off for the remainder of tier-1)."""
    lib = get_lib()
    if lib is not None:
        _bind_wire(lib, strict=False)
        _bind_codec(lib, strict=False)
        _bind_assemble(lib, strict=False)
        _bind_featurize(lib, strict=False)


def available() -> bool:
    return get_lib() is not None


# every entry point the C sources export; the fast host path is LIVE only
# when all of them bound
SYMBOLS = (
    "fasthash_batch", "pad_units_batch", "pad_units_batch_u8",
    "lexicon_score_batch", "parse_tweet_block", "parse_tweet_block_wire",
    "digram_encode", "wire_assemble", "featurize_wire",
)


def require_live() -> dict:
    """The measurement entry points' gate (``chip_smoke.py``, ``benchmark/``):
    return {"lib", "stamp", "symbols"} when the library was built on this
    host from the tracked sources and EVERY symbol bound; raise otherwise —
    a host-bound number taken on the Python fallback is a tenth of the real
    one with nothing said."""
    lib = get_lib()
    if lib is None:
        raise RuntimeError(
            "native fast path is not live: native/libfasthash.so could not "
            "be built or loaded on this host (is g++ on PATH?) — refusing "
            "to measure the Python fallback"
        )
    missing = [name for name in SYMBOLS if not hasattr(lib, name)]
    if missing:
        raise RuntimeError(
            f"native fast path is degraded: symbol(s) {missing} did not "
            "bind — refusing to measure the fallback"
        )
    return {"lib": _LIB, "stamp": read_stamp(), "symbols": list(SYMBOLS)}


def _thread_count_from_env() -> int:
    """TWTML_NATIVE_THREADS: <=0 or unset/non-integer = auto (the C side
    picks hardware concurrency, capped, scaled down for small batches)."""
    try:
        return int(os.environ.get("TWTML_NATIVE_THREADS", "0"))
    except ValueError:
        log.warning("TWTML_NATIVE_THREADS is not an integer; using auto")
        return 0


def encode_texts(texts: list[str]) -> tuple[np.ndarray, np.ndarray]:
    """One-pass UTF-16-LE encode of a batch: (units, offsets). Callers reuse
    the offsets for token-bucket sizing so texts are encoded exactly once.

    One join + one encode instead of per-text encodes (2048 small encodes
    were ~40% of the whole featurize hot path). UTF-16-LE is BOM-free and
    concatenation-safe, so per-text unit counts are all that's needed to
    split the joined buffer: len(t) when every char is BMP (1 unit each),
    with a per-text re-encode only in the rare astral-emoji case."""
    joined = "".join(texts)
    # surrogatepass: json.loads produces lone surrogates (escaped \uD800 or
    # raw surrogate UTF-8 bytes, which it decodes permissively); the JVM
    # ground truth treats them as ordinary units (features/hashing.py)
    units = np.frombuffer(
        joined.encode("utf-16-le", "surrogatepass"), dtype=np.uint16
    )
    offsets = np.zeros(len(texts) + 1, dtype=np.int64)
    if units.size == len(joined):  # no astral chars: 1 unit per char
        counts = [len(t) for t in texts]
    else:
        counts = [
            len(t) if t.isascii()
            else len(t.encode("utf-16-le", "surrogatepass")) >> 1
            for t in texts
        ]
    np.cumsum(counts, out=offsets[1:])
    if units.size == 0:
        units = np.zeros(1, dtype=np.uint16)
    return units, offsets


def pad_units(
    encoded: tuple[np.ndarray, np.ndarray],
    n: int,
    padded_rows: int,
    l_max: int,
    ascii_lower: bool = False,
    narrow: bool = False,
) -> tuple[np.ndarray, np.ndarray] | None:
    """Ragged (units, offsets) → ([padded_rows, l_max] units, [padded_rows]
    int32 lengths) via the C row-memcpy loop; None if the library is
    unavailable (caller falls back to the numpy gather). ``ascii_lower``
    folds 'A'-'Z' during the copy. ``narrow`` writes a uint8 buffer — the
    half-width wire format for batches the caller KNOWS are byte-ranged
    (ascii-flagged rows); it is metadata-driven, never sniffed from data."""
    lib = get_lib()
    if lib is None:
        return None
    units, offsets = encoded
    if narrow:
        buf: np.ndarray = np.empty((padded_rows, l_max), dtype=np.uint8)
        fn, ptr_t = lib.pad_units_batch_u8, ctypes.c_uint8
    else:
        buf = np.empty((padded_rows, l_max), dtype=np.uint16)
        fn, ptr_t = lib.pad_units_batch, ctypes.c_uint16
    length = np.empty((padded_rows,), dtype=np.int32)
    max_len = fn(
        units.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n,
        padded_rows,
        l_max,
        1 if ascii_lower else 0,
        buf.ctypes.data_as(ctypes.POINTER(ptr_t)),
        length.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    if max_len > l_max:  # caller sized l_max from these offsets; never expected
        return None
    return buf, length


def hash_texts(
    texts: list[str],
    num_features: int,
    out_idx: np.ndarray,
    out_val: np.ndarray,
    encoded: tuple[np.ndarray, np.ndarray] | None = None,
) -> np.ndarray | None:
    """Hash lowercased texts into the caller's padded [B, L] buffers.
    Returns per-row distinct-term counts, or None if the native path is
    unavailable or L was too small (caller should re-bucket or fall back).
    ``encoded``: optional pre-computed (units, offsets) from encode_texts."""
    lib = get_lib()
    if lib is None:
        return None
    b, l_max = out_idx.shape
    assert len(texts) <= b
    units, offsets = encoded if encoded is not None else encode_texts(texts)
    assert offsets.size == len(texts) + 1, "encoded does not match texts"
    ntok = np.zeros(b, dtype=np.int32)

    max_terms = lib.fasthash_batch(
        units.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(texts),
        num_features,
        l_max,
        out_idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        out_val.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ntok.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        _thread_count_from_env(),
    )
    if max_terms > l_max or (ntok[: len(texts)] < 0).any():
        # token bucket too small, or a row overflowed the C scratch table
        return None
    return ntok


def parse_tweet_block(
    data: bytes,
    begin: int,
    end: int,
    cap_rows: int = 0,
    copy: bool = True,
) -> tuple | None:
    """Parse newline-delimited tweet JSON with the C data-loader, applying
    the isRetweet + [begin, end] retweet-count filter in-line.

    Returns (numeric int64 [rows, 5] = {label, followers, favourites,
    friends, created_ms}, units uint16 (concatenated), offsets int64
    [rows+1], ascii uint8 [rows], consumed_bytes, bad_lines) — or None when
    the C library is unavailable (callers fall back to the Python
    json.loads + Status path, the semantic ground truth).

    ``copy=False`` returns views into the freshly allocated backing buffers
    (each call allocates its own, so views never alias across calls) —
    skips ~n bytes of memcpy per call on the streaming hot path, at the
    price of pinning the worst-case-sized buffers for the block's life;
    right for blocks consumed promptly, wrong for long-lived accumulation."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(data)
    if cap_rows <= 0:
        # upper-bound rows without scanning for newlines: real tweet lines
        # are hundreds of bytes, so n/64 over-provisions; pathological
        # shorter lines just trip the parser's clean early-stop and the
        # caller continues from *consumed (same contract as cap_units)
        cap_rows = max(16, n >> 6)
    # total text units from n input bytes is < n; the parser additionally
    # reserves one full row (kMaxTextUnits) of headroom before each line,
    # so size past that to never trip the early-stop mid-block
    cap_units = n + MAX_TEXT_UNITS + 1
    numeric = np.empty((cap_rows, 5), dtype=np.int64)
    units = np.empty((cap_units,), dtype=np.uint16)
    offsets = np.empty((cap_rows + 1,), dtype=np.int64)
    ascii_flags = np.empty((cap_rows,), dtype=np.uint8)
    consumed = ctypes.c_int64(0)
    bad = ctypes.c_int64(0)
    rows = lib.parse_tweet_block(
        data,
        n,
        begin,
        end,
        cap_rows,
        cap_units,
        numeric.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        units.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ascii_flags.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.byref(consumed),
        ctypes.byref(bad),
    )
    # default: copies, not views — the backing buffers are sized for the
    # worst case (~3 bytes per input byte) and callers accumulate blocks
    if copy:
        return (
            numeric[:rows].copy(),
            units[: offsets[rows]].copy(),
            offsets[: rows + 1].copy(),
            ascii_flags[:rows].copy(),
            int(consumed.value),
            int(bad.value),
        )
    return (
        numeric[:rows],
        units[: offsets[rows]],
        offsets[: rows + 1],
        ascii_flags[:rows],
        int(consumed.value),
        int(bad.value),
    )


def wire_available() -> bool:
    """Whether the zero-copy wire emitter is loadable (the library is up
    and carries the symbol — see _bind_wire's degrade seam)."""
    return get_lib() is not None and not _wire_missing


def parse_tweet_block_wire(
    data: bytes,
    begin: int,
    end: int,
    cap_rows: int = 0,
    copy: bool = True,
) -> tuple | None:
    """One C pass from raw block bytes to the ragged wire's unit
    representation (native/tweetjson.cpp parse_tweet_block_wire): same
    kept rows / numeric / offsets / ascii as ``parse_tweet_block``, but the
    units come back **uint8** whenever every kept row is ASCII (the narrow
    wire — no separate downcast pass) and uint16 otherwise (the parser
    widens its committed prefix ONCE, in C, when the first non-ASCII row
    commits). Returns the same tuple shape as ``parse_tweet_block``
    (numeric, units, offsets, ascii, consumed, bad) — callers can treat
    the two interchangeably — or None when the C library is unavailable
    OR predates the wire emitter (``_wire_missing``; callers fall back to
    the ParsedBlock path, which keeps working on old symbol sets).

    Both unit buffers are allocated with ``np.empty`` up front; the wide
    one stays untouched (no page faults) unless a row actually widens, so
    the common ASCII stream never pays for it."""
    lib = get_lib()
    if lib is None or _wire_missing:
        return None
    n = len(data)
    if cap_rows <= 0:
        cap_rows = max(16, n >> 6)  # same over-provision rule as above
    cap_units = n + MAX_TEXT_UNITS + 1
    numeric = np.empty((cap_rows, 5), dtype=np.int64)
    units_u8 = np.empty((cap_units,), dtype=np.uint8)
    units_u16 = np.empty((cap_units,), dtype=np.uint16)
    offsets = np.empty((cap_rows + 1,), dtype=np.int64)
    ascii_flags = np.empty((cap_rows,), dtype=np.uint8)
    consumed = ctypes.c_int64(0)
    bad = ctypes.c_int64(0)
    narrow = ctypes.c_int64(0)
    needs_wide = ctypes.c_int64(0)
    rows = lib.parse_tweet_block_wire(
        data,
        n,
        begin,
        end,
        cap_rows,
        cap_units,
        numeric.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        units_u8.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        units_u16.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ascii_flags.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.byref(consumed),
        ctypes.byref(bad),
        ctypes.byref(narrow),
        ctypes.byref(needs_wide),
    )
    if needs_wide.value:  # can't happen: a wide buffer is always passed
        raise RuntimeError("wire parser requested a wide buffer it was given")
    units = units_u8 if narrow.value else units_u16
    total = int(offsets[rows]) if rows else 0
    if copy:
        return (
            numeric[:rows].copy(),
            units[:total].copy(),
            offsets[: rows + 1].copy(),
            ascii_flags[:rows].copy(),
            int(consumed.value),
            int(bad.value),
        )
    return (
        numeric[:rows],
        units[:total],
        offsets[: rows + 1],
        ascii_flags[:rows],
        int(consumed.value),
        int(bad.value),
    )


def lexicon_scores(
    encoded: tuple[np.ndarray, np.ndarray],
    n: int,
    pos_lex: tuple[np.ndarray, np.ndarray, np.ndarray],
    neg_lex: tuple[np.ndarray, np.ndarray, np.ndarray],
) -> np.ndarray | None:
    """Batch lexicon sentiment scores over ragged UTF-16 units (uint16, or
    the narrow wire's uint8: read in place, no widened copy).

    ``pos_lex``/``neg_lex`` are (words_units, word_offsets, word_hashes)
    from features/sentiment.py's packed lexicons (at most 64 words a list
    and 31 units a word: ``_pack_lexicon`` asserts it). Returns scores int32 [n],
    exact for every row (the scan handles non-ASCII units itself). None
    when the C library is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    units, offsets = encoded
    assert offsets.size == n + 1, "encoded does not match the batch"
    assert units.dtype.itemsize in (1, 2), units.dtype
    score = np.empty((n,), dtype=np.int32)
    pw, po, ph = pos_lex
    nw, no, nh = neg_lex
    lib.lexicon_score_batch(
        units.ctypes.data,
        units.dtype.itemsize,
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n,
        pw.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
        po.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ph.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        len(ph),
        nw.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
        no.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        nh.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        len(nh),
        score.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    return score
