"""Native (C++) runtime components with build-on-first-use + ctypes.

The reference implements its runtime hot paths in Rust/C++; here the
compute path is JAX/XLA and the host-side hot structures get C++ cores
(radix_tree.cpp so far). No pybind11 in the image, so bindings are plain
ctypes over a C ABI; the shared object compiles from source on first use
(g++ is baked into the image) and callers fall back to the pure-Python
implementation if compilation fails or DTPU_NATIVE=0.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess

from dynamo_tpu.runtime.logging import get_logger

log = get_logger("native")

_DIR = os.path.dirname(os.path.abspath(__file__))


def load_library(name: str) -> ctypes.CDLL | None:
    """Load (building if needed) lib ``name`` (e.g. "radix_tree" ->
    _radix_tree.<source hash>.so). The object's name carries the hash of
    the source it was built from, so a stale one left in the tree — by an
    older checkout, or by a copy that reset mtimes — can never stand in
    for the committed source. Returns None when native is disabled or
    the build fails (logged; doctor.check_native reports it)."""
    if os.environ.get("DTPU_NATIVE", "1").lower() in ("0", "false"):
        return None
    src = os.path.join(_DIR, f"{name}.cpp")
    with open(src, "rb") as fh:
        digest = hashlib.sha256(fh.read()).hexdigest()[:12]
    so = os.path.join(_DIR, f"_{name}.{digest}.so")
    if not os.path.exists(so):
        tmp = f"{so}.{os.getpid()}.tmp"  # concurrent builders can't collide
        try:
            subprocess.run(
                ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", src,
                 "-o", tmp],
                check=True, capture_output=True, text=True, timeout=120)
            os.replace(tmp, so)
            log.info("built native %s", so)
        except (subprocess.CalledProcessError, subprocess.TimeoutExpired,
                OSError) as exc:
            detail = getattr(exc, "stderr", "") or str(exc)
            log.warning("native build of %s failed (%s); using the Python "
                        "implementation", name, detail[:500])
            try:
                os.unlink(tmp)  # pid-unique names would otherwise accumulate
            except OSError:
                pass
            return None
        for old in glob.glob(os.path.join(_DIR, f"_{name}*.so")):
            if old != so:
                try:
                    os.unlink(old)  # built from a source that is gone
                except OSError:
                    pass
    try:
        return ctypes.CDLL(so)
    except OSError as exc:
        log.warning("could not load %s (%s); using the Python "
                    "implementation", so, exc)
        return None
