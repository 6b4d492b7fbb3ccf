"""ctypes bindings to the native media runtime (native/libtpurtc.so).

The library is built from ``native/*.cpp`` with make on first use and is
never committed (the library itself has zero build-time deps; libavcodec is
dlopen'd at runtime).  Consumers that have a pure-python path handle a
``None`` from :func:`load` (media/codec.py NullCodec, media/rtp.py); the
native-rtp provider has none and calls :func:`require`.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess

logger = logging.getLogger(__name__)

_NATIVE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "native")
)
_LIB_PATH = os.path.join(_NATIVE_DIR, "libtpurtc.so")

_lib = None
_lib_tried = False
_load_error = ""


def _build():
    """make the library under a private name, then rename it into place:
    several processes may start in a fresh checkout at once, and none may
    dlopen a half-written file."""
    tmp = f"libtpurtc.so.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["make", "-C", _NATIVE_DIR, f"OUT={tmp}"],
            check=True, capture_output=True, text=True, timeout=300,
        )
        os.replace(os.path.join(_NATIVE_DIR, tmp), _LIB_PATH)
    finally:
        try:
            os.remove(os.path.join(_NATIVE_DIR, tmp))
        except FileNotFoundError:
            pass


def load() -> ctypes.CDLL | None:
    global _lib, _lib_tried, _load_error
    if _lib_tried:
        return _lib
    _lib_tried = True
    if not os.path.exists(_LIB_PATH):
        try:
            _build()
        except (subprocess.SubprocessError, OSError) as e:
            _load_error = f"building {_LIB_PATH} failed: {e}" + (
                f"\n{e.stderr}" if getattr(e, "stderr", None) else ""
            )
            logger.warning("%s; using python fallbacks", _load_error)
            return None
    try:
        lib = ctypes.CDLL(_LIB_PATH)
    except OSError as e:
        _load_error = f"cannot load {_LIB_PATH} ({e})"
        logger.warning("%s", _load_error)
        return None
    _declare(lib)
    _lib = lib
    return lib


def require() -> ctypes.CDLL:
    """The library or a RuntimeError carrying why it could not be built or
    loaded — for callers with no python fallback (the native-rtp provider
    when it was asked for by name)."""
    lib = load()
    if lib is None:
        raise RuntimeError(f"native media runtime unavailable: {_load_error}")
    return lib


def _declare(lib: ctypes.CDLL):
    c = ctypes
    u8p = c.POINTER(c.c_uint8)

    lib.tr_ring_create.restype = c.c_void_p
    lib.tr_ring_create.argtypes = [c.c_size_t, c.c_size_t]
    lib.tr_ring_destroy.argtypes = [c.c_void_p]
    lib.tr_ring_try_push.restype = c.c_int
    lib.tr_ring_try_push.argtypes = [c.c_void_p, u8p, c.c_int64, c.c_int64]
    lib.tr_ring_push_latest.restype = c.c_int
    lib.tr_ring_push_latest.argtypes = [c.c_void_p, u8p, c.c_int64, c.c_int64]
    lib.tr_ring_try_pop.restype = c.c_int64
    lib.tr_ring_try_pop.argtypes = [c.c_void_p, u8p, c.c_int64, c.POINTER(c.c_int64)]
    lib.tr_ring_size.restype = c.c_int64
    lib.tr_ring_size.argtypes = [c.c_void_p]
    lib.tr_ring_dropped.restype = c.c_int64
    lib.tr_ring_dropped.argtypes = [c.c_void_p]

    lib.tr_rtp_packetizer_create.restype = c.c_void_p
    lib.tr_rtp_packetizer_create.argtypes = [c.c_uint32, c.c_uint8, c.c_int32]
    lib.tr_rtp_packetizer_destroy.argtypes = [c.c_void_p]
    lib.tr_rtp_packetize.restype = c.c_int64
    lib.tr_rtp_packetize.argtypes = [
        c.c_void_p, u8p, c.c_int64, c.c_uint32, u8p, c.c_int64,
    ]
    lib.tr_rtp_depacketizer_create.restype = c.c_void_p
    lib.tr_rtp_depacketizer_destroy.argtypes = [c.c_void_p]
    lib.tr_rtp_depacketize.restype = c.c_int
    lib.tr_rtp_depacketize.argtypes = [c.c_void_p, u8p, c.c_int64]
    lib.tr_rtp_get_au.restype = c.c_int64
    lib.tr_rtp_get_au.argtypes = [c.c_void_p, u8p, c.c_int64, c.POINTER(c.c_uint32)]

    lib.tr_h264_available.restype = c.c_int
    lib.tr_h264_encoder_create.restype = c.c_void_p
    lib.tr_h264_encoder_create.argtypes = [
        c.c_int, c.c_int, c.c_int, c.c_int, c.c_int64, c.c_int, c.c_char_p, c.c_char_p,
    ]
    if hasattr(lib, "tr_h264_encoder_create_rc"):  # absent in pre-r3 builds
        lib.tr_h264_encoder_create_rc.restype = c.c_void_p
        lib.tr_h264_encoder_create_rc.argtypes = [
            c.c_int, c.c_int, c.c_int, c.c_int, c.c_int64, c.c_int64,
            c.c_int64, c.c_int, c.c_char_p, c.c_char_p,
        ]
    lib.tr_h264_encode.restype = c.c_int64
    lib.tr_h264_encode.argtypes = [
        c.c_void_p, u8p, c.c_int64, u8p, c.c_int64, c.POINTER(c.c_int),
    ]
    lib.tr_h264_encoder_destroy.argtypes = [c.c_void_p]
    if hasattr(lib, "tr_h264_force_keyframe"):  # absent in pre-r3 builds
        lib.tr_h264_force_keyframe.argtypes = [c.c_void_p]
    if hasattr(lib, "tr_h264_encoder_reconfigure"):
        # in-place rate control (absent in committed pre-r6 builds: codec.py
        # falls back to rebuild-on-next-IDR when this export is missing)
        lib.tr_h264_encoder_reconfigure.argtypes = [
            c.c_void_p, c.c_int64, c.c_int, c.c_int,
        ]
    lib.tr_h264_decoder_create.restype = c.c_void_p
    lib.tr_h264_decode.restype = c.c_int64
    lib.tr_h264_decode.argtypes = [
        c.c_void_p, u8p, c.c_int64, c.c_int64, u8p, c.c_int64,
        c.POINTER(c.c_int), c.POINTER(c.c_int), c.POINTER(c.c_int64),
    ]
    lib.tr_h264_decoder_destroy.argtypes = [c.c_void_p]


def h264_available() -> bool:
    lib = load()
    return bool(lib and lib.tr_h264_available())
