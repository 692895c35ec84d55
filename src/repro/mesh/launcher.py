"""``jax.distributed`` multi-process launcher + env-var attach.

Two halves of one contract:

* :func:`launch` — the CPU harness for tests/CI: spawn N python
  processes on localhost, pinned to the CPU backend
  (``JAX_PLATFORMS=cpu`` — a chip belongs to one process, and gloo
  collectives are CPU-only), each wired to a fresh coordinator through the
  ``REPRO_MESH_*`` environment variables, run a target per process and
  collect its output.  The target is either a ``"pkg.mod:fn"`` spec
  (re-entered via ``python -m repro.mesh.launcher``) or a script path
  (run as ``python script.py args...`` — the script calls
  :func:`attach` itself).

* :func:`attach` — env-var attach for children AND real clusters: read
  the ``REPRO_MESH_*`` variables (a scheduler can set the same ones),
  force the per-process XLA host device count *before* jax loads, pick
  the gloo CPU collectives backend, and ``jax.distributed.initialize``.
  With no variables set it is a no-op returning the single-process view
  — safe to call unconditionally at program start.

Environment variables::

    REPRO_MESH_COORDINATOR    host:port of process 0's coordinator
    REPRO_MESH_NUM_PROCESSES  total process count N
    REPRO_MESH_PROCESS_ID     this process's id in [0, N)
    REPRO_MESH_LOCAL_DEVICES  devices per process (CPU: forces the XLA
                              host device count; unset = platform default)

Importing this module never touches jax (children must set XLA flags
before jax loads — that is the point).
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import os
import socket
import subprocess
import sys
from typing import Dict, List, Optional, Sequence

ENV_COORDINATOR = "REPRO_MESH_COORDINATOR"
ENV_NUM_PROCESSES = "REPRO_MESH_NUM_PROCESSES"
ENV_PROCESS_ID = "REPRO_MESH_PROCESS_ID"
ENV_LOCAL_DEVICES = "REPRO_MESH_LOCAL_DEVICES"

__all__ = ["attach", "launch", "pick_coordinator", "mesh_env",
           "LaunchError", "LaunchResult",
           "ENV_COORDINATOR", "ENV_NUM_PROCESSES", "ENV_PROCESS_ID",
           "ENV_LOCAL_DEVICES"]


class LaunchError(RuntimeError):
    """A launched process died; carries every process's output tail."""


def pick_coordinator(host: str = "127.0.0.1") -> str:
    """A free ``host:port`` for a fresh coordinator (bind-and-release)."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind((host, 0))
        return f"{host}:{s.getsockname()[1]}"


def mesh_env(coordinator: str, num_processes: int, process_id: int,
             local_devices: Optional[int] = None) -> Dict[str, str]:
    """The ``REPRO_MESH_*`` variables for one process of a job."""
    env = {
        ENV_COORDINATOR: coordinator,
        ENV_NUM_PROCESSES: str(int(num_processes)),
        ENV_PROCESS_ID: str(int(process_id)),
    }
    if local_devices is not None:
        env[ENV_LOCAL_DEVICES] = str(int(local_devices))
    return env


def attach(verbose: bool = False) -> Dict[str, object]:
    """Join the mesh described by the ``REPRO_MESH_*`` environment.

    Must run before anything initialises jax's backends.  Returns a
    summary dict; ``attached`` is False when no coordinator is set (the
    plain single-process path — nothing is touched).
    """
    coordinator = os.environ.get(ENV_COORDINATOR)
    if not coordinator:
        return {"attached": False, "process_id": 0, "num_processes": 1}
    num_processes = int(os.environ[ENV_NUM_PROCESSES])
    process_id = int(os.environ[ENV_PROCESS_ID])
    local = os.environ.get(ENV_LOCAL_DEVICES)
    if local and "jax" not in sys.modules:
        flags = os.environ.get("XLA_FLAGS", "")
        flags = " ".join(f for f in flags.split()
                         if not f.startswith("--xla_force_host_platform_"
                                             "device_count"))
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={local}".strip())
    import jax
    # TCP collectives for cross-process all_to_all on CPU hosts; a pure
    # config flag, ignored by non-CPU platforms (and probing the backend
    # here would initialise it, which initialize() forbids)
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    info = {"attached": True, "coordinator": coordinator,
            "process_id": process_id, "num_processes": num_processes,
            "local_devices": int(jax.local_device_count())}
    if verbose:
        print(f"[mesh.attach] p{process_id}/{num_processes} -> {coordinator} "
              f"({info['local_devices']} local devices)", flush=True)
    return info


@dataclasses.dataclass
class LaunchResult:
    coordinator: str
    returncodes: List[int]
    outputs: List[str]          # combined stdout+stderr per process

    def output(self, process_id: int = 0) -> str:
        return self.outputs[process_id]


def _child_cmd(target: str, args: Sequence[str], python: str) -> List[str]:
    if target.endswith(".py") or os.path.sep in target:
        return [python, target, *map(str, args)]
    return [python, "-m", "repro.mesh.launcher", target,
            json.dumps(list(map(str, args)))]


def launch(target: str, n_processes: int, *, args: Sequence[str] = (),
           local_devices: int = 1, env: Optional[Dict[str, str]] = None,
           timeout_s: float = 600.0, python: str = sys.executable
           ) -> LaunchResult:
    """Run ``target`` in ``n_processes`` coordinator-connected processes.

    Every child gets the ``REPRO_MESH_*`` variables, ``JAX_PLATFORMS=cpu``
    and an XLA flag forcing ``local_devices`` host devices (overriding any
    inherited forced count — the parent's device fan-out must not leak
    into children).  Raises :class:`LaunchError` if any process exits
    non-zero or exceeds ``timeout_s``.
    """
    coordinator = pick_coordinator()
    procs = []
    for pid in range(int(n_processes)):
        child_env = dict(os.environ)
        child_env.update(env or {})
        child_env.update(mesh_env(coordinator, n_processes, pid,
                                  local_devices))
        child_env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={local_devices}")
        child_env["JAX_PLATFORMS"] = "cpu"
        procs.append(subprocess.Popen(
            _child_cmd(target, args, python), env=child_env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outputs: List[str] = []
    returncodes: List[int] = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout_s)
            outputs.append(out or "")
            returncodes.append(p.returncode)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        while len(outputs) < len(procs):
            p = procs[len(outputs)]
            try:
                out, _ = p.communicate()
            except Exception:
                out = ""
            outputs.append(out or "")
            returncodes.append(p.returncode if p.returncode is not None
                               else -1)
        raise LaunchError(
            f"launch({target!r}, n={n_processes}) timed out after "
            f"{timeout_s}s; tails:\n" + _tails(outputs))
    if any(rc != 0 for rc in returncodes):
        raise LaunchError(
            f"launch({target!r}, n={n_processes}) failed "
            f"(returncodes={returncodes}); tails:\n" + _tails(outputs))
    return LaunchResult(coordinator, returncodes, outputs)


def _tails(outputs: List[str], lines: int = 25) -> str:
    parts = []
    for pid, out in enumerate(outputs):
        tail = "\n".join(out.splitlines()[-lines:])
        parts.append(f"--- process {pid} ---\n{tail}")
    return "\n".join(parts)


def _child_main(argv: List[str]) -> int:
    """``python -m repro.mesh.launcher pkg.mod:fn '[json args]'`` — the
    module:function child entry: attach, import, call."""
    if not argv:
        print("usage: python -m repro.mesh.launcher pkg.mod:fn '[args...]'",
              file=sys.stderr)
        return 2
    target = argv[0]
    call_args = json.loads(argv[1]) if len(argv) > 1 else []
    attach(verbose=True)
    mod_name, _, fn_name = target.partition(":")
    fn = getattr(importlib.import_module(mod_name), fn_name)
    fn(*call_args)
    return 0


if __name__ == "__main__":
    raise SystemExit(_child_main(sys.argv[1:]))
