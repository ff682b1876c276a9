"""Build-at-first-use for the bundled C++ sources.

The binaries are never committed: each checkout compiles its own with
the local ``g++``.  Freshness is decided by content, not by mtime — a
copy of the tree onto another machine resets every mtime, and a binary
that merely looks newer than its source may have been built from a
different one.  The sha256 of the source and of the compile command is
written next to the binary (``<lib>.so.srchash``) and the library is
rebuilt whenever it differs.
"""

from __future__ import annotations

import fcntl
import hashlib
import os
import subprocess
from typing import List, Sequence


def _source_hash(src: str, commands: Sequence[Sequence[str]]) -> str:
    h = hashlib.sha256()
    with open(src, "rb") as f:
        h.update(f.read())
    h.update(repr([list(c) for c in commands]).encode())
    return h.hexdigest()


def _recorded_hash(so: str) -> str:
    try:
        with open(so + ".srchash") as f:
            return f.read().strip()
    except OSError:
        return ""


def ensure_built(
    src: str, so: str, commands: Sequence[List[str]], force: bool = False
) -> None:
    """Compile ``src`` into ``so`` unless ``so`` was built from exactly
    this source by exactly these commands (flock-guarded so concurrent
    workers don't race).

    ``commands`` are g++ argument lists without the source and output
    (appended here); each is tried in turn and the first that compiles
    wins.  ``force`` rebuilds even when the recorded hash matches — used
    when dlopen rejects a binary built by a foreign toolchain.
    """
    want = _source_hash(src, commands)

    def fresh() -> bool:
        return os.path.exists(so) and _recorded_hash(so) == want

    def _stat_sig():
        try:
            st = os.stat(so)
            return (st.st_mtime_ns, st.st_size, st.st_ino)
        except OSError:
            return None

    if not force and fresh():
        return
    pre_lock_sig = _stat_sig()
    with open(so + ".lock", "w") as lf:
        fcntl.flock(lf, fcntl.LOCK_EX)
        if not force and fresh():
            return
        if force and _stat_sig() != pre_lock_sig:
            # a peer that held the flock first already replaced the
            # binary — N workers failing dlopen together must not each
            # run a full recompile back-to-back
            return
        tmp = so + ".tmp"
        for i, cmd in enumerate(commands):
            try:
                subprocess.run(
                    list(cmd) + [src, "-o", tmp],
                    check=True, capture_output=True,
                )
                break
            except subprocess.CalledProcessError:
                if i == len(commands) - 1:
                    raise
        os.replace(tmp, so)
        with open(so + ".srchash.tmp", "w") as f:
            f.write(want + "\n")
        os.replace(so + ".srchash.tmp", so + ".srchash")
