"""``procs.Children.stop``: every child a run started is signalled and
waited for, however long another takes to go."""

from __future__ import annotations

import subprocess

import pytest

from chipbench import procs

IGNORES_SIGTERM = ("import signal, time; signal.signal(signal.SIGTERM, signal.SIG_IGN); "
                   "print('up', flush=True); time.sleep(120)")


def test_stop_kills_what_sigterm_does_not_end(tmp_path, monkeypatch, capfd):
    monkeypatch.setattr(procs, "TERM_WAIT_S", 0.5)
    children = procs.Children(tmp_path, tmp_path)
    children.start("easy", ["-c", "import time; time.sleep(120)"], {})
    log = children.start("stubborn", ["-c", IGNORES_SIGTERM], {})
    procs.wait_for(lambda: "up" in log.read_text(), children, 30, "the handler")
    started = [proc for _, proc, _ in children.procs]
    children.stop()
    assert [proc.returncode for proc in started] == [-15, -9]
    assert children.procs == []
    said = capfd.readouterr().err
    assert "stubborn killed 0.5 s after SIGTERM, gone" in said
    assert "easy left" in said


class _Unreapable:
    """A child that SIGKILL does not end in time, as a worker stuck in the
    chip's driver would be."""

    pid = 2 ** 22 + 12345   # above any pid_max in use: killpg finds no such group
    returncode = None

    def __init__(self):
        self.signals = []

    def poll(self):
        return None

    def send_signal(self, sig):
        self.signals.append(sig)

    def wait(self, timeout):
        raise subprocess.TimeoutExpired("worker", timeout)


def test_stop_signals_every_child_before_it_reports_one_that_stays(tmp_path, monkeypatch):
    monkeypatch.setattr(procs, "TERM_WAIT_S", 0.1)
    children = procs.Children(tmp_path, tmp_path)
    children.start("store", ["-c", "import time; time.sleep(120)"], {})
    store = children.procs[0][1]
    children.procs.append(("worker-0", _Unreapable(), tmp_path / "worker-0.log"))
    with pytest.raises(procs.HarnessFault, match=r"after SIGKILL: \['worker-0'\]"):
        children.stop()
    assert store.returncode == -15   # stopped although the worker before it was not
