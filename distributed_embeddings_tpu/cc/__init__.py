"""Native (C++) components, loaded via ctypes.

The reference builds its native code into ``_embedding_lookup_ops.so`` with
nvcc (`/root/reference/Makefile:38-52`); here TPU device code is Pallas
(``ops/pallas_apply.py``) and the native host code — the data loader — is
built by the Makefile in this directory into ``_data_loader.so``.

``load_data_loader()`` returns the ctypes library, (re)building it on first
use when ``data_loader.cc`` is newer than the ``.so`` (``make`` decides —
the ``.so`` is git-ignored, so a checkout may carry a stale one or none);
callers fall back to the numpy path when it returns None, which is logged
once.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading

logger = logging.getLogger(__name__)

_CC_DIR = os.path.dirname(os.path.abspath(__file__))
_SO_PATH = os.path.join(_CC_DIR, "_data_loader.so")

_lock = threading.Lock()
_lib = None
_load_attempted = False


def _configure(lib: ctypes.CDLL) -> ctypes.CDLL:
  lib.de_loader_open.restype = ctypes.c_void_p
  lib.de_loader_open.argtypes = [
      ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
      ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int64),
      ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
      ctypes.c_int, ctypes.c_int, ctypes.c_int,
  ]
  lib.de_loader_error.restype = ctypes.c_char_p
  lib.de_loader_error.argtypes = [ctypes.c_void_p]
  lib.de_loader_num_samples.restype = ctypes.c_int64
  lib.de_loader_num_samples.argtypes = [ctypes.c_void_p]
  lib.de_loader_num_batches.restype = ctypes.c_int64
  lib.de_loader_num_batches.argtypes = [ctypes.c_void_p]
  lib.de_loader_start.restype = None
  lib.de_loader_start.argtypes = [ctypes.c_void_p]
  lib.de_loader_next.restype = ctypes.c_int64
  lib.de_loader_next.argtypes = [
      ctypes.c_void_p, ctypes.POINTER(ctypes.c_float),
      ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_float),
  ]
  lib.de_loader_close.restype = None
  lib.de_loader_close.argtypes = [ctypes.c_void_p]
  return lib


def build(force: bool = False) -> bool:
  """Bring ``_data_loader.so`` up to date with ``data_loader.cc`` (``make``
  compares their timestamps; ``force`` rebuilds regardless); returns
  success."""
  try:
    subprocess.run(["make", "-C", _CC_DIR, "-s"] + (["-B"] if force else []),
                   check=True, capture_output=True, timeout=120)
    return os.path.exists(_SO_PATH)
  except (subprocess.SubprocessError, OSError) as e:
    detail = getattr(e, "stderr", b"") or b""
    logger.warning("native data loader build failed: %s %s", e,
                   detail.decode(errors="replace")[-500:])
    return False


def load_data_loader():
  """ctypes handle to the native loader, or None if unavailable."""
  global _lib, _load_attempted
  with _lock:
    if _lib is not None or _load_attempted:
      return _lib
    _load_attempted = True
    if build():
      try:
        _lib = _configure(ctypes.CDLL(_SO_PATH))
      except OSError as e:
        logger.warning("native data loader failed to load: %s", e)
    if _lib is None:
      logger.warning("native data loader unavailable: the numpy reader "
                     "path is used instead")
    return _lib
