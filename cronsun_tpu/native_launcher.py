"""Shared launcher for the native (C++) servers.

Both native servers — ``cronsun-stored`` (coordination store) and
``cronsun-logd`` (result store) — are supervised the same way: locate
or build the binary from ``native/``, spawn it with ``--die-with-parent``,
hand secrets over in a 0600 temp file (argv is world-readable), wait for
the READY line, and expose monitor/stop.  One definition here; the
per-server modules add only their flag sets.
"""

from __future__ import annotations

import fcntl
import os
import pathlib
import select
import shutil
import subprocess
import threading
import time
from typing import Callable, List, Optional

from . import log

NATIVE_DIR = pathlib.Path(__file__).resolve().parents[1] / "native"


def find_binary(name: str, env_var: str, build: bool = True) -> Optional[str]:
    """Locate a native server binary: $<env_var>, then the repo's
    native/ build, then $PATH.  With ``build``, compile from source when
    the binary is missing or older than its sources."""
    env = os.environ.get(env_var)
    if env and os.access(env, os.X_OK):
        return env
    cand = NATIVE_DIR / name
    srcs = [NATIVE_DIR / f"{name.split('-', 1)[1]}.cc", NATIVE_DIR / "njson.h"]
    if srcs[0].exists() and build:
        # one builder at a time across processes, and nobody judges the
        # binary fresh while another is still linking it: every xdist
        # worker and every `bin.store --native` comes through here
        try:
            with open(NATIVE_DIR / ".build.lock", "w") as lock:
                fcntl.flock(lock, fcntl.LOCK_EX)
                stale = (not cand.exists() or any(
                    s.exists() and cand.stat().st_mtime < s.stat().st_mtime
                    for s in srcs))
                if stale:
                    subprocess.run(["make", "-C", str(NATIVE_DIR), name],
                                   check=True, capture_output=True,
                                   timeout=120)
        except (subprocess.SubprocessError, OSError) as e:
            log.warnf("native build of %s failed: %s", name, e)
    if cand.exists() and os.access(cand, os.X_OK):
        return str(cand)
    return shutil.which(name)


class NativeProcess:
    """A supervised native server child: spawn, READY-parse, monitor,
    stop.  ``port=0`` picks a free port (resolved from the READY line)."""

    def __init__(self, binary: str, argv_tail: List[str], token: str = "",
                 ready_timeout: float = 10.0):
        argv = [binary] + argv_tail + ["--die-with-parent"]
        token_path = None
        if token:
            import tempfile
            tfd, token_path = tempfile.mkstemp(prefix="cronsun-tok-")
            os.write(tfd, token.encode())
            os.close(tfd)
            argv += ["--token-file", token_path]
        # stderr merged into stdout so a startup failure (bind error …)
        # surfaces in the exception instead of vanishing
        try:
            self._proc = subprocess.Popen(
                argv, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True)
            self._stopping = False
            line = self._read_ready(ready_timeout)
        finally:
            if token_path:
                try:
                    os.unlink(token_path)
                except OSError:
                    pass
        addr = line.split(" ", 1)[1]
        self.host, port_s = addr.rsplit(":", 1)
        self.port = int(port_s)

    def _read_ready(self, timeout: float) -> str:
        """Bounded wait for the READY line; on failure, kill the child and
        raise with whatever it printed."""
        fd = self._proc.stdout.fileno()
        deadline = time.monotonic() + timeout
        lines: List[str] = []
        while time.monotonic() < deadline:
            r, _, _ = select.select([fd], [], [],
                                    max(0.0, deadline - time.monotonic()))
            if not r:
                break
            line = self._proc.stdout.readline()
            if not line:        # EOF: child exited
                break
            lines.append(line)
            if line.startswith("READY "):
                return line.strip()
        self._proc.kill()
        raise RuntimeError(
            f"native server failed to start within {timeout}s: "
            f"{''.join(lines).strip()!r}")

    def monitor(self, on_exit: Callable[[int], None]):
        """Watch the child; call ``on_exit(rc)`` if it dies without
        :meth:`stop` — so a supervising process doesn't sit
        healthy-looking in front of a dead server."""
        def run():
            rc = self._proc.wait()
            if not self._stopping:
                on_exit(rc)
        threading.Thread(target=run, daemon=True,
                         name="native-server-monitor").start()

    def start(self):
        return self     # already serving (READY consumed in __init__)

    def stop(self):
        self._stopping = True
        if self._proc.poll() is None:
            self._proc.terminate()
            try:
                self._proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                self._proc.kill()
