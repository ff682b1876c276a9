"""``chipbench/chips.py`` and the end of a run (``run._stop_cluster``):
a run starts and ends when the chips can be opened, with device files
that only answer what the test tells them to."""

import errno
import os
import subprocess
import sys
import textwrap

import pytest

from chipbench import chips, contract


@pytest.fixture
def device(monkeypatch):
    """One fake device file whose ``os.open`` answers the errnos of
    ``answers`` in turn (0 or the end of the list: it opens)."""
    answers, asked = [], []
    real_open = os.open

    def fake_open(path, flags, *a, **kw):
        if path != "/dev/vfio/0":
            return real_open(path, flags, *a, **kw)
        asked.append(path)
        code = answers.pop(0) if answers else 0
        if code:
            raise OSError(code, os.strerror(code), path)
        return real_open(os.devnull, os.O_RDONLY)

    monkeypatch.setattr(chips, "device_files", lambda: ["/dev/vfio/0"])
    monkeypatch.setattr(chips.os, "open", fake_open)
    monkeypatch.setattr(chips, "POLL_S", 0.05)
    return answers, asked


def test_busy_twice_then_free_waits_and_returns_the_seconds(device):
    answers, asked = device
    answers += [errno.EBUSY, errno.EBUSY]
    waited = chips.wait_until_free(5.0)
    assert 0.1 <= waited < 1.0
    assert len(asked) == 3


@pytest.mark.parametrize("code", [errno.EACCES, errno.ENODEV, errno.ENOENT])
def test_another_error_is_not_busy(device, code):
    answers, _ = device
    answers += [code]
    assert chips.busy() == []
    answers += [code]
    assert chips.wait_until_free(5.0) == 0.0


def test_busy_for_ever_raises_at_the_ceiling_and_names_the_file(device):
    answers, _ = device
    answers += [errno.EBUSY] * 1000
    with pytest.raises(chips.ChipsBusy, match=r"after 0\.3 s still busy: /dev/vfio/0 held by"):
        chips.wait_until_free(0.3)


def test_a_host_without_device_files_waits_for_nothing(monkeypatch):
    monkeypatch.setattr(chips.glob, "glob", lambda pattern: [])
    assert chips.device_files() == [] and chips.busy() == []
    assert chips.wait_until_free(0.0) == 0.0


def test_device_files_are_those_the_program_counts(monkeypatch):
    found = {"/dev/accel*": [], "/dev/vfio/*": ["/dev/vfio/vfio", "/dev/vfio/1", "/dev/vfio/0"]}
    monkeypatch.setattr(chips.glob, "glob", lambda pattern: found[pattern])
    assert chips.device_files() == ["/dev/vfio/0", "/dev/vfio/1"]
    found["/dev/accel*"] = ["/dev/accel1", "/dev/accel0"]
    assert chips.device_files() == ["/dev/accel0", "/dev/accel1"]


def test_holders_names_the_pid_that_has_the_file_open(tmp_path):
    path = str(tmp_path / "device")
    fd = os.open(path, os.O_CREAT | os.O_RDWR)
    try:
        assert os.getpid() in chips.holders([path])
        assert str(os.getpid()) in chips.describe([path])
    finally:
        os.close(fd)
    assert os.getpid() not in chips.holders([path])


# ``_stop_cluster`` kills every descendant of the process it runs in, so
# it runs in a process of its own.  The child starts a grandchild and
# both sleep; the SIGKILL meant for the grandchild is swallowed, as a
# holder of chips outlives its SIGKILL while the kernel takes its
# mappings down, so it ends by itself ``lives_s`` later.  The device
# file ``/dev/vfio/0`` answers busy for ``busy_s`` after the kill.
_SCRIPT = textwrap.dedent("""
    import os, subprocess, sys, time
    import ray_tpu  # before the clock starts: _stop_cluster imports it
    from chipbench import run

    lives_s, ceiling_s, busy_s = map(float, sys.argv[1:4])
    child = subprocess.Popen(
        [sys.executable, "-c",
         "import subprocess, sys, time;"
         f"g = subprocess.Popen([sys.executable, '-c', 'import time; time.sleep({lives_s})']);"
         "print(g.pid, flush=True); time.sleep(60)"],
        stdout=subprocess.PIPE, text=True)
    grandchild = int(child.stdout.readline())
    real_kill = os.kill
    run.os.kill = lambda pid, sig: None if pid == grandchild else real_kill(pid, sig)
    t0 = time.monotonic()
    run.chips.busy = lambda: ["/dev/vfio/0"] if time.monotonic() - t0 < busy_s else []
    run._stop_cluster(ceiling_s)
    took = time.monotonic() - t0
    print(f"took={took:.2f} child_alive={run._alive(child.pid)} "
          f"grandchild_alive={run._alive(grandchild)}", flush=True)
    try:
        real_kill(grandchild, 9)
    except OSError:
        pass
""")


def _stop(lives_s, ceiling_s, busy_s=0.0):
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(lives_s), str(ceiling_s), str(busy_s)],
        cwd=contract.ROOT, capture_output=True, text=True, timeout=60,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    fields = dict(f.split("=") for f in out.stdout.split())
    return float(fields["took"]), fields, out.stderr


def test_the_end_waits_for_a_grandchild_that_outlives_its_killed_parent():
    took, fields, err = _stop(lives_s=2.0, ceiling_s=20)
    assert 1.0 <= took < 10.0
    assert fields["child_alive"] == fields["grandchild_alive"] == "False"
    assert "still alive" not in err


def test_the_end_gives_up_at_its_ceiling_and_says_who_is_left():
    took, fields, err = _stop(lives_s=30, ceiling_s=0.5)
    assert 0.5 <= took < 5.0
    assert fields["child_alive"] == "False" and fields["grandchild_alive"] == "True"
    assert "0.5 s after the cluster was stopped: pid(s) [" in err
    assert "still alive; ending all the same" in err


def test_the_end_waits_until_the_chips_can_be_opened_again():
    took, fields, err = _stop(lives_s=0, ceiling_s=20, busy_s=1.5)
    assert 1.5 <= took < 10.0
    assert "busy" not in err and "held by" not in err
    took, fields, err = _stop(lives_s=0, ceiling_s=0.5, busy_s=30)
    assert 0.5 <= took < 5.0
    assert "0.5 s after the cluster was stopped: /dev/vfio/0 held by pid(s) unknown" in err
