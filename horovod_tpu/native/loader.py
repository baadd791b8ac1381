"""Loader for the native core extension with graceful fallback.

First use triggers an in-tree compile (``build.py``) when a C++ toolchain is
available; otherwise — or if the build fails — every consumer falls back to
the pure-Python implementation of the same algorithm.  Set
``HOROVOD_TPU_NATIVE_CORE=0`` to skip the native path entirely, or
``HOROVOD_TPU_NATIVE_BUILD=0`` to disallow the on-demand build.
"""

from __future__ import annotations

import logging
import os

logger = logging.getLogger("horovod_tpu")

_core = None
_attempted = False


def _disabled() -> bool:
    # same boolean semantics as Config.use_native_core (config._env_bool):
    # anything other than 1/true/yes/on disables
    from ..config import _env_bool
    return not _env_bool("HOROVOD_TPU_NATIVE_CORE", True)


def load(auto_build: bool = True):
    """Import ``_hvd_core``, building it on demand; returns module or None.

    Only an artefact at least as new as ``core.cpp`` is imported: a stale
    one is rebuilt (or, where building is not allowed, left alone) rather
    than loaded in place of the source that sits beside it.
    """
    global _core, _attempted
    if _disabled():
        return None
    if _attempted:
        return _core
    from . import build
    if not build.built():
        build_env = os.environ.get(
            "HOROVOD_TPU_NATIVE_BUILD", "1").strip().lower()
        if not auto_build or build_env in ("0", "false", "no", "off"):
            # not a full attempt: leave memoization open so a later caller
            # that allows building can still succeed
            return None
    _attempted = True
    try:
        if build.build():
            from . import _hvd_core  # type: ignore
            _core = _hvd_core
            logger.debug("native core loaded: %s", _hvd_core.__file__)
    except Exception:  # noqa: BLE001 - any failure means Python fallback
        logger.warning("native core unavailable; using the Python control "
                       "plane", exc_info=True)
        _core = None
    return _core


def reset():
    global _core, _attempted
    _core = None
    _attempted = False
