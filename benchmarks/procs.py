"""The fleet's processes: spawn, READY, CPU seconds, orderly end.
Copied from ``chip_smoke.py`` ``Fleet`` (the yardstick keeps its own)."""

import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TICK = os.sysconf("SC_CLK_TCK")


class RunFailure(Exception):
    """The run cannot be judged: no result line, exit code 1."""


def tail(path: str, n: int = 30) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError as e:
        return f"<{e}>"


class Fleet:
    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.procs = {}                  # name -> (Popen, log path)
        self.stopped = set()

    def spawn(self, name: str, argv: list, env: dict = None):
        path = os.path.join(self.log_dir, f"{name}.log")
        with open(path, "wb") as log:
            # the environment goes through untouched: the scheduler
            # child finds its chip and its compile cache as JAX would
            p = subprocess.Popen([sys.executable, *argv], cwd=REPO,
                                 stdout=log, stderr=subprocess.STDOUT,
                                 env=env, start_new_session=True)
        self.procs[name] = (p, path)
        return p, path

    def line(self, name: str, prefix: str):
        """The rest of the first complete log line starting with
        ``prefix``, or None."""
        with open(self.procs[name][1], errors="replace") as f:
            for line in f:
                if line.startswith(prefix) and line.endswith("\n"):
                    return line[len(prefix):].strip()
        return None

    def await_line(self, name: str, prefix: str, timeout: float) -> str:
        p, path = self.procs[name]
        deadline = time.time() + timeout
        while time.time() < deadline:
            got = self.line(name, prefix)
            if got is not None:
                return got
            if p.poll() is not None:
                raise RunFailure(f"{name} exited rc={p.returncode} before "
                                 f"{prefix!r}:\n" + tail(path))
            time.sleep(0.1)
        raise RunFailure(f"no {prefix!r} from {name} within "
                         f"{timeout:.0f}s:\n" + tail(path))

    def log_has(self, name: str, needle: str) -> int:
        with open(self.procs[name][1], errors="replace") as f:
            return f.read().count(needle)

    def check_alive(self):
        for name, (p, path) in self.procs.items():
            if p.poll() is not None and name not in self.stopped:
                raise RunFailure(f"{name} died rc={p.returncode}:\n"
                                 + tail(path))

    def cpu_seconds(self) -> dict:
        """CPU seconds so far of each child, its reaped children (the
        commands an agent forks) included."""
        ticks, parent = {}, {}
        for pid in os.listdir("/proc"):
            if not pid.isdigit():
                continue
            try:
                with open(f"/proc/{pid}/stat") as f:
                    f_ = f.read().rsplit(")", 1)[1].split()
                parent[int(pid)] = int(f_[1])
                ticks[int(pid)] = sum(int(f_[i]) for i in (11, 12, 13, 14))
            except (OSError, IndexError, ValueError):
                continue            # it ended while we were reading

        def tree(pid):
            # bin.store --native serves from a child (the C++ daemon)
            return ticks.get(pid, 0) + sum(
                tree(c) for c, pp in parent.items() if pp == pid)
        return {name: tree(p.pid) / _TICK
                for name, (p, _path) in self.procs.items()}

    def maps_jax(self, name: str) -> bool:
        try:
            with open(f"/proc/{self.procs[name][0].pid}/maps") as f:
                return "jaxlib" in f.read()
        except OSError:
            return False

    def stop(self, prefix: str, grace: float) -> dict:
        """SIGTERM every child whose name starts with ``prefix``; what
        outlasts ``grace`` seconds is killed.  Returns {name: code}."""
        batch = [(n, p) for n, (p, _) in self.procs.items()
                 if n.startswith(prefix)]
        for name, p in batch:
            self.stopped.add(name)
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        rcs = {}
        deadline = time.time() + grace
        for name, p in batch:
            try:
                rcs[name] = p.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
                rcs[name] = "killed"
        return rcs

    def kill_all(self):
        """Nothing this run started outlives it: every child is the
        leader of a session of its own, and the whole group goes (the
        native store's daemon, a command an agent had just forked)."""
        for name, (p, _path) in self.procs.items():
            self.stopped.add(name)
            try:
                os.killpg(p.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        for p, _path in self.procs.values():
            p.wait()
