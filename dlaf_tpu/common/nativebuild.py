"""Shared native-build plumbing.

``atomic_build`` is the one copy of the concurrent-safe g++ compile
discipline (flock'd lockfile, temp file + atomic rename, stale re-check
under the lock) used by both the native kernels (``native/__init__.py``)
and the C-ABI shim (``capi/__init__.py``).
"""
from __future__ import annotations

import os
import subprocess
import tempfile
from typing import Iterable, Sequence


def _warn_build_failure(out_so: str, last_err: str | None) -> None:
    """A failed native build silently degrades to slow fallbacks; leave a
    diagnosable trace (suppressible via DLAF_TPU_QUIET_BUILD=1)."""
    if os.environ.get("DLAF_TPU_QUIET_BUILD"):
        return
    import warnings

    warnings.warn(
        f"native build of {os.path.basename(out_so)} failed; falling back to "
        f"pure-Python paths. Last compiler error:\n{last_err or '(no output)'}",
        RuntimeWarning,
        stacklevel=3,
    )


def atomic_build(
    sources: Sequence[str],
    out_so: str,
    flag_variants: Iterable[Sequence[str]],
    timeout: int = 300,
    deps: Sequence[str] = (),
) -> bool:
    """Compile ``sources`` into ``out_so`` with g++, trying each flag
    variant in order.  Builds to a temp file and atomically renames so
    concurrent processes (or a package dir shared across hosts) never
    observe a half-written .so; cross-process exclusion via an flock'd
    lockfile.  Staleness = out_so older than ANY source or dep (``deps``
    are staleness inputs only — e.g. #included headers — and are NOT put
    on the compile command line).  Returns True on success (including when
    another process finished the build first)."""

    def fresh() -> bool:
        if not os.path.exists(out_so):
            return False
        t = os.path.getmtime(out_so)
        return all(
            t >= os.path.getmtime(s)
            for s in (*sources, *deps)
            if os.path.exists(s)
        )

    if fresh():
        return True
    here = os.path.dirname(os.path.abspath(out_so))
    lock_f = None
    try:
        import fcntl

        lock_f = open(out_so + ".lock", "w")
        fcntl.flock(lock_f, fcntl.LOCK_EX)
    except Exception:
        lock_f = None
    tmp = None
    try:
        if fresh():  # another process built while we waited on the lock
            return True
        fd, tmp = tempfile.mkstemp(suffix=".so", dir=here)
        os.close(fd)
        last_err = None
        for flags in flag_variants:
            cmd = ["g++", "-shared", "-fPIC", "-o", tmp, *sources, *flags]
            try:
                r = subprocess.run(cmd, capture_output=True, text=True, timeout=timeout)
            except Exception as e:
                last_err = f"{cmd[0]}: {e}"
                continue
            if r.returncode == 0:
                os.chmod(tmp, 0o755)
                os.rename(tmp, out_so)
                return True
            last_err = r.stderr.strip()[-2000:]
        _warn_build_failure(out_so, last_err)
        return False
    except Exception as e:
        _warn_build_failure(out_so, repr(e))
        return False
    finally:
        if tmp is not None and os.path.exists(tmp):
            try:
                os.unlink(tmp)
            except OSError:
                pass
        if lock_f is not None:
            lock_f.close()
