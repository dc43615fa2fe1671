"""ctypes loader for the native control-plane library.

Reference equivalent: horovod/common/basics.py:22 — ``HorovodBasics`` loads
the C core with ``ctypes.CDLL`` and the Python layer calls through it. Here
the library (csrc/ → lib/libhorovod_tpu.so) carries the control plane (stats,
response cache, fusion planner, timeline writer, message wire format, GP/EI
autotuner, bf16 converters); the first use runs the in-tree Makefile, which
rebuilds it only when csrc/ is newer, and if no toolchain is available every
consumer falls back to its pure-Python mirror (the behavior contract is
identical — tests run against both).
"""

import ctypes
import os
import subprocess
import threading

from .utils.logging import get_logger

_logger = get_logger()
_lock = threading.Lock()
_lib = None
_tried = False

_PKG_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_PKG_DIR, "lib", "libhorovod_tpu.so")
_CSRC_DIR = os.path.join(os.path.dirname(_PKG_DIR), "csrc")


def _declare(lib):
    c = ctypes
    lib.hvd_stats_new.restype = c.c_void_p
    lib.hvd_stats_free.argtypes = [c.c_void_p]
    lib.hvd_stats_record.argtypes = [c.c_void_p, c.c_char_p, c.c_int64,
                                     c.c_int64]
    lib.hvd_stats_counter.argtypes = [c.c_void_p, c.c_char_p]
    lib.hvd_stats_counter.restype = c.c_int64
    lib.hvd_stats_total_time_us.argtypes = [c.c_void_p, c.c_char_p]
    lib.hvd_stats_total_time_us.restype = c.c_int64
    lib.hvd_stats_write_file.argtypes = [c.c_void_p, c.c_char_p]
    lib.hvd_stats_write_file.restype = c.c_int
    lib.hvd_stats_histogram.argtypes = [c.c_void_p, c.c_char_p,
                                        c.POINTER(c.c_int64),
                                        c.POINTER(c.c_int64),
                                        c.POINTER(c.c_int64), c.c_int]
    lib.hvd_stats_histogram.restype = c.c_int

    lib.hvd_cache_new.argtypes = [c.c_int]
    lib.hvd_cache_new.restype = c.c_void_p
    lib.hvd_cache_free.argtypes = [c.c_void_p]
    lib.hvd_cache_lookup.argtypes = [c.c_void_p, c.c_char_p]
    lib.hvd_cache_lookup.restype = c.c_int
    lib.hvd_cache_put.argtypes = [c.c_void_p, c.c_char_p]
    lib.hvd_cache_remove.argtypes = [c.c_void_p, c.c_char_p]
    for fn in (lib.hvd_cache_hits, lib.hvd_cache_misses, lib.hvd_cache_size):
        fn.argtypes = [c.c_void_p]
        fn.restype = c.c_int64

    lib.hvd_fusion_plan.argtypes = [
        c.POINTER(c.c_int64), c.POINTER(c.c_int32), c.c_int, c.c_int64,
        c.POINTER(c.c_int32)]
    lib.hvd_fusion_plan.restype = c.c_int
    lib.hvd_fusion_offsets.argtypes = [c.POINTER(c.c_int64), c.c_int,
                                       c.POINTER(c.c_int64)]
    lib.hvd_fusion_offsets.restype = c.c_int64

    lib.hvd_timeline_new.argtypes = [c.c_char_p, c.c_int]
    lib.hvd_timeline_new.restype = c.c_void_p
    lib.hvd_timeline_event.argtypes = [c.c_void_p, c.c_char_p, c.c_char_p,
                                       c.c_char, c.c_int64, c.c_int]
    lib.hvd_timeline_cycle.argtypes = [c.c_void_p, c.c_int64]
    lib.hvd_timeline_close.argtypes = [c.c_void_p]
    lib.hvd_timeline_counter.argtypes = [c.c_void_p, c.c_char_p,
                                         c.c_int64, c.c_double]

    lib.hvd_request_list_serialize.restype = c.c_int64
    lib.hvd_request_list_parse.restype = c.c_int

    lib.hvd_bo_new.argtypes = [c.c_int, c.POINTER(c.c_double),
                               c.POINTER(c.c_double), c.c_double, c.c_uint64]
    lib.hvd_bo_new.restype = c.c_void_p
    lib.hvd_bo_free.argtypes = [c.c_void_p]
    lib.hvd_bo_add_sample.argtypes = [c.c_void_p, c.POINTER(c.c_double),
                                      c.c_int, c.c_double]
    lib.hvd_bo_suggest.argtypes = [c.c_void_p, c.POINTER(c.c_double), c.c_int]

    for fn in (lib.hvd_f32_to_bf16, lib.hvd_f32_to_f16):
        fn.argtypes = [c.POINTER(c.c_float), c.POINTER(c.c_uint16), c.c_int64]
    for fn in (lib.hvd_bf16_to_f32, lib.hvd_f16_to_f32):
        fn.argtypes = [c.POINTER(c.c_uint16), c.POINTER(c.c_float), c.c_int64]
    lib.hvd_bf16_sum.argtypes = [c.POINTER(c.c_uint16),
                                 c.POINTER(c.c_uint16),
                                 c.POINTER(c.c_uint16), c.c_int64]
    return lib


def _build():
    """Bring lib/libhorovod_tpu.so up to date with csrc/ by running
    ``make`` — a no-op when the library is newer than every source, so a
    library left over from an older csrc/ is never loaded. Returns False
    when the build ran and failed (the library on disk is then stale)."""
    try:
        subprocess.run(["make", "-s"], cwd=_CSRC_DIR, check=True,
                       capture_output=True, timeout=120)
    except FileNotFoundError as e:
        # no make on this host: a library that exists was put there by
        # whoever installed the package, and nothing here can date it
        _logger.info("native library build skipped: %s", e)
    except (subprocess.SubprocessError, OSError) as e:
        _logger.warning("native library build failed, using the "
                        "pure-Python control plane: %s",
                        getattr(e, "stderr", b"") or e)
        return False
    return True


def get_lib():
    """The native library handle, or None when unavailable (pure-Python
    fallbacks take over)."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.path.isdir(_CSRC_DIR) and not _build():
            return None
        if os.path.exists(_LIB_PATH):
            try:
                _lib = _declare(ctypes.CDLL(_LIB_PATH))
                _logger.info("loaded native control plane: %s", _LIB_PATH)
            except OSError as e:
                _logger.warning("could not load native library: %s", e)
        return _lib


def available():
    return get_lib() is not None
