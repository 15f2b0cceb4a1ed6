"""Shell command framework (reference weed/shell/commands.go).

Commands register themselves into COMMANDS via the @command decorator (the
reference's init() appends to shell.Commands).  Each command is
`fn(env, argv) -> str`; CommandEnv carries the master connection plus the
cluster-wide admin lock every mutating command must hold
(confirmIsLocked, shell/commands.go:73; exclusive_locker.go:28-129).
"""

from __future__ import annotations

import io
import shlex
import threading
from typing import Callable

from ..pb.rpc import POOL, RpcError

COMMANDS: dict[str, Callable] = {}
HELP: dict[str, str] = {}


def command(name: str, help_text: str = ""):
    def deco(fn):
        COMMANDS[name] = fn
        HELP[name] = help_text or (fn.__doc__ or "").strip().splitlines()[0] \
            if (help_text or fn.__doc__) else ""
        return fn
    return deco


class ShellError(Exception):
    pass


class CommandEnv:
    def __init__(self, master_grpc: str):
        self.master_grpc = master_grpc
        self._token = 0
        self._lock_stop: threading.Event | None = None

    def master(self):
        return POOL.client(self.master_grpc, "Seaweed")

    def volume_server(self, grpc_addr: str):
        return POOL.client(grpc_addr, "VolumeServer")

    def topology(self) -> dict:
        return self.master().call("VolumeList")["topology"]

    # -- admin lock --------------------------------------------------------
    def lock(self, client_name: str = "shell") -> None:
        out = self.master().call("LeaseAdminToken", {
            "previous_token": self._token, "client_name": client_name})
        self._token = out["token"]
        # renew every ~3s like exclusive_locker.go:95
        stop = threading.Event()
        self._lock_stop = stop

        def renew():
            while not stop.wait(3.0):
                try:
                    out = self.master().call("LeaseAdminToken", {
                        "previous_token": self._token,
                        "client_name": client_name})
                    self._token = out["token"]
                except RpcError:
                    break

        threading.Thread(target=renew, daemon=True).start()

    def unlock(self) -> None:
        if self._lock_stop:
            self._lock_stop.set()
        if self._token:
            try:
                self.master().call("ReleaseAdminToken",
                                   {"previous_token": self._token})
            except RpcError:
                pass
            self._token = 0

    def confirm_is_locked(self) -> None:
        if not self._token:
            raise ShellError(
                "lock is lost, or it was never acquired: run `lock` first")


def run_command(env: CommandEnv, line: str) -> str:
    argv = shlex.split(line)
    if not argv:
        return ""
    name, args = argv[0], argv[1:]
    if name == "help":
        return "\n".join(f"{n}\t{HELP.get(n, '')}"
                         for n in sorted(COMMANDS))
    if name == "lock":
        env.lock()
        return "locked"
    if name == "unlock":
        env.unlock()
        return "unlocked"
    fn = COMMANDS.get(name)
    if fn is None:
        raise ShellError(f"unknown command: {name}")
    return fn(env, args) or ""


# -- shared topology-walk helpers (used by several commands) ---------------

def iter_data_nodes(topo: dict):
    """Yield (dc_id, rack_id, node_dict) from a VolumeList topology dump."""
    for dc in topo.get("data_centers", []):
        for rack in dc.get("racks", []):
            for dn in rack.get("data_nodes", []):
                yield dc["id"], rack["id"], dn


def node_grpc(dn: dict) -> str:
    host = dn.get("ip") or dn["id"].split(":")[0]
    return f"{host}:{dn.get('grpc_port', 0)}"


def node_tcp(dn: dict) -> str:
    """The node's raw-TCP frame port (volume_server/tcp.py)."""
    host = dn.get("ip") or dn["id"].split(":")[0]
    return f"{host}:{dn.get('tcp_port', 0)}"


def parse_flags(args: list[str]) -> dict[str, str]:
    """-volumeId 3 -collection x -force  ->  {volumeId: '3', ...}."""
    out: dict[str, str] = {}
    i = 0
    while i < len(args):
        a = args[i]
        if a.startswith("-"):
            key = a.lstrip("-")
            if i + 1 < len(args) and not args[i + 1].startswith("-"):
                out[key] = args[i + 1]
                i += 2
            else:
                out[key] = "true"
                i += 1
        else:
            i += 1
    return out
