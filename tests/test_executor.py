"""Executor: capture, exit codes, launch, timeout, retry, parallels gate."""

import os
import pwd
import shutil
import subprocess
import sys
import tempfile
import threading
import time

import pytest

from cronsun_tpu.node import executor as executor_mod
from cronsun_tpu.node.executor import Executor

IS_ROOT = os.geteuid() == 0
# pid, pgid and sid of the command itself, then its uid and gid
IDS = "sh -c 'echo $$ $(ps -o pgid=,sid= -p $$) $(id -u) $(id -g)'"


@pytest.fixture
def ex():
    return Executor()


@pytest.fixture
def popen_kwargs(monkeypatch):
    """Every keyword the executor hands ``subprocess.Popen``."""
    seen = []
    real = subprocess.Popen

    def spy(*a, **kw):
        seen.append(kw)
        return real(*a, **kw)
    monkeypatch.setattr(executor_mod.subprocess, "Popen", spy)
    return seen


def test_success_captures_stdout(ex):
    r = ex.run_once("echo hello world")
    assert r.success and r.exit_code == 0
    assert r.output.strip() == "hello world"


def test_failure_exit_code(ex):
    r = ex.run_once("false")
    assert not r.success and r.exit_code == 1
    assert "exit status 1" in r.error


def test_stderr_combined(ex):
    r = ex.run_once("sh -c 'echo out; echo err >&2'")
    assert "out" in r.output and "err" in r.output


def test_quoted_arguments(ex):
    r = ex.run_once("echo 'one two'  three")
    assert r.output.strip() == "one two three"


def test_missing_binary(ex):
    r = ex.run_once("definitely-not-a-real-binary-xyz")
    assert not r.success and r.error


def test_empty_command(ex):
    r = ex.run_once("")
    assert not r.success and "empty command" in r.error


def test_unknown_user(ex, popen_kwargs):
    r = ex.run_once("echo hi", user="no-such-user-xyz")
    assert not r.success and r.error == "user 'no-such-user-xyz' not found"
    assert not popen_kwargs, "an unknown user must not reach the launch"
    assert r.spawn_s == 0.0 and not r.demoted


def _other_user():
    """An account that is not the suite's own, for the demotion cases."""
    for name in ("nobody", "daemon"):
        try:
            info = pwd.getpwnam(name)
        except KeyError:
            continue
        if info.pw_uid != os.geteuid():
            return info
    pytest.skip("no second account on this machine")


@pytest.mark.parametrize("user", [
    "",
    pytest.param("other", marks=pytest.mark.skipif(
        not IS_ROOT, reason="demotion needs root")),
])
def test_launch_runs_no_python_between_fork_and_exec(ex, popen_kwargs, user):
    """The mechanism, not a timing: no ``preexec_fn`` ever (CPython then
    runs nothing of Python in the child, and takes ``vfork()`` where no
    uid/gid changes); session, group and demotion are Popen's own.  The
    child leads its own session and group either way — ``killpg(pid)``
    on a timeout reaches what it reached before."""
    info = _other_user() if user else None
    r = ex.run_once(IDS, user=info.pw_name if info else "")
    assert r.success, r
    (kw,) = popen_kwargs
    assert kw.get("preexec_fn") is None
    assert kw["start_new_session"] is True
    pid, pgid, sid, uid, gid = map(int, r.output.split())
    assert pid == pgid == sid
    if info:
        assert (kw["user"], kw["group"]) == (info.pw_uid, info.pw_gid)
        assert (uid, gid) == (info.pw_uid, info.pw_gid)
        assert "extra_groups" not in kw     # supplementary groups as before
    else:
        assert kw.get("user") is None and kw.get("group") is None
        assert (uid, gid) == (os.geteuid(), os.getegid())
    assert r.demoted is bool(info)
    assert r.spawn_s > 0


_NOT_ROOT = """
import sys
sys.path.insert(0, sys.argv[1])
import executor
r = executor.Executor().run_once("echo hi", user="root")
print(repr((r.success, r.error, r.demoted, r.spawn_s > 0)))
"""


def test_demotion_refused_is_a_recorded_failure(ex):
    """An agent that is not root cannot demote: the OS's refusal comes
    back through Popen as a PermissionError and is a recorded failure.
    (With ``preexec_fn`` it was a SubprocessError, which is no OSError
    and escaped ``run_once``.)  A root suite sheds its privileges in a
    child interpreter, on a copy of the module: ``/root`` is closed to
    everyone else."""
    want = "(False, '[Errno 1] Operation not permitted', True, True)"
    if not IS_ROOT:
        r = ex.run_once("echo hi", user="root")
        got = repr((r.success, r.error, r.demoted, r.spawn_s > 0))
        assert got == want
        return
    info = _other_user()
    d = tempfile.mkdtemp(prefix="exec-notroot-", dir="/tmp")
    try:
        os.chmod(d, 0o755)
        shutil.copy(executor_mod.__file__, os.path.join(d, "executor.py"))
        os.chmod(os.path.join(d, "executor.py"), 0o644)
        p = subprocess.run(
            [sys.executable, "-S", "-c", _NOT_ROOT, d], cwd="/",
            user=info.pw_uid, group=info.pw_gid, extra_groups=[],
            env={"PYTHONDONTWRITEBYTECODE": "1"},
            capture_output=True, text=True, timeout=60)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    if p.returncode and "Permission denied" in p.stderr:
        pytest.skip(f"{info.pw_name} cannot run {sys.executable}")
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == want


@pytest.mark.parametrize("leaves", ["nothing", "grandchild"])
def test_timeout_kills_process_group(ex, tmp_path, leaves):
    """The timeout's ``killpg(proc.pid)`` takes everything the command
    left in its group: a background grandchild dies with it."""
    pidfile = tmp_path / "grandchild.pid"
    cmd = "sh -c 'sleep 30'" if leaves == "nothing" else \
        f"sh -c 'sleep 31 & echo $! > {pidfile}; sleep 30'"
    t0 = time.time()
    r = ex.run_once(cmd, timeout=1)
    assert time.time() - t0 < 5
    assert not r.success and r.error == "timeout after 1s"
    assert r.exit_code == -9
    if leaves == "grandchild":
        gpid = int(pidfile.read_text())
        deadline = time.time() + 5
        while time.time() < deadline:
            try:    # gone, or a zombie waiting for init
                with open(f"/proc/{gpid}/stat") as f:
                    if f.read().rsplit(")", 1)[1].split()[0] == "Z":
                        break
            except FileNotFoundError:
                break
            time.sleep(0.05)
        else:
            pytest.fail(f"grandchild {gpid} outlived the timeout's killpg")


def test_output_truncation():
    ex = Executor(max_output=100)
    r = ex.run_once("sh -c 'yes x | head -c 10000'")
    assert len(r.output) < 200 and "[truncated]" in r.output


def test_retry_until_success(ex, tmp_path):
    flag = tmp_path / "flag"
    cmd = f"sh -c 'test -f {flag} && exit 0 || {{ touch {flag}; exit 1; }}'"
    r = ex.run_job("j1", cmd, retry=3)
    assert r.success and r.retries_used == 1


def test_retry_exhausted(ex):
    slept = []
    r = ex.run_job("j2", "false", retry=2, interval=1,
                   sleep=lambda s: slept.append(s))
    assert not r.success and r.retries_used == 2
    assert slept == [1, 1]


def test_parallels_gate_skips(ex):
    started = threading.Event()
    release = threading.Event()
    results = {}

    def long_run():
        started.set()
        results["long"] = ex.run_job(
            "j3", "sh -c 'sleep 2'", parallels=1)

    t = threading.Thread(target=long_run)
    t.start()
    started.wait()
    time.sleep(0.2)  # ensure the gate is held
    r = ex.run_job("j3", "echo quick", parallels=1)
    assert r.skipped and not r.success
    t.join()
    # gate released afterwards
    r2 = ex.run_job("j3", "echo again", parallels=1)
    assert r2.success


def test_run_duration_recorded(ex):
    r = ex.run_once("sh -c 'sleep 0.2'")
    assert 0.15 <= r.seconds < 2.0
