"""Whether the host's TPU chips can be opened: the one question a run
asks before it starts and before it ends.

A chip belongs to one process at a time, and a process that held chips
lets go of them seconds after it is gone (its device file answers
``EBUSY`` 3-6 s after a SIGTERM or an exit: PERF.md section 6, PR 44).
The device files are those ``ray_tpu/accelerators/tpu.py:_detect``
counts.  No jax, no cluster: opening a device file and closing it at
once claims nothing.  A host without such files (``--rehearse``, the
CPU) has nothing busy and waits for nothing.
"""

from __future__ import annotations

import errno
import glob
import os
import time

#: how often ``wait_until_free`` asks again
POLL_S = 0.2


class ChipsBusy(RuntimeError):
    """The chips were still held when the ceiling was reached."""


def device_files() -> list:
    accel = sorted(glob.glob("/dev/accel*"))
    return accel or sorted(
        p for p in glob.glob("/dev/vfio/*") if p != "/dev/vfio/vfio"
    )


def busy() -> list:
    """The device files that answer ``EBUSY``.  Any other error (no
    permission, no such device) is not "busy": the program reports it
    when it opens the chips itself."""
    held = []
    for path in device_files():
        try:
            os.close(os.open(path, os.O_RDWR))
        except OSError as e:
            if e.errno == errno.EBUSY:
                held.append(path)
    return held


def holders(paths) -> list:
    """Pids that ``/proc/*/fd`` shows with one of ``paths`` open (those
    this user may look into; a holder of another user stays unnamed)."""
    pids = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            fds = os.listdir(f"/proc/{pid}/fd")
        except OSError:
            continue
        for fd in fds:
            try:
                if os.readlink(f"/proc/{pid}/fd/{fd}") in paths:
                    pids.append(int(pid))
                    break
            except OSError:
                continue
    return sorted(pids)


def describe(held) -> str:
    return f"{', '.join(held)} held by pid(s) {holders(held) or 'unknown'}"


def wait_until_free(ceiling_s: float) -> float:
    """Return, as soon as no device file is busy, the seconds waited
    (0.0 where none was); raise ``ChipsBusy`` naming the files and
    their holders once ``ceiling_s`` have passed."""
    t0 = time.monotonic()
    held = busy()
    if not held:
        return 0.0
    while time.monotonic() - t0 < ceiling_s:
        time.sleep(POLL_S)
        held = busy()
        if not held:
            return time.monotonic() - t0
    raise ChipsBusy(f"after {ceiling_s:g} s still busy: {describe(held)}")
