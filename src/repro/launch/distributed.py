"""`jax.distributed` bring-up for multi-process CPU fleets.

Multi-controller SPMD on plain CPUs: every process runs the *same*
program, contributes ``REPRO_DIST_LOCAL_DEVICES`` forced-host CPU devices
to one global mesh, and the cluster-major shard_map round's two psums run
as real cross-process collectives (gloo).  Worker processes must call
:func:`initialize_from_env` **before importing jax-heavy modules** — it
appends ``--xla_force_host_platform_device_count`` to ``XLA_FLAGS``,
which XLA reads once at backend init.

    # parent: spawn 2 workers of this very script
    from repro.launch.distributed import spawn_local
    results = spawn_local([sys.argv[0], "--dist-worker"], n_procs=2,
                          local_devices=2)

    # worker (top of the script, before `import jax`):
    from repro.launch.distributed import initialize_from_env
    initialize_from_env()

The env-var contract (``REPRO_DIST_COORD`` / ``_NPROC`` / ``_PID`` /
``_LOCAL_DEVICES``) also works under an external launcher (mpirun, srun,
k8s indexed jobs): export the four variables per rank and call
:func:`initialize_from_env` — no CLI coupling.

This module deliberately does not import jax at module scope, so it is
importable before the worker's XLA_FLAGS are final.
"""
from __future__ import annotations

import os
import socket
import subprocess
import sys
import time
from typing import List, Optional, Sequence

ENV_COORD = "REPRO_DIST_COORD"            # host:port of process 0
ENV_NPROC = "REPRO_DIST_NPROC"            # total process count
ENV_PID = "REPRO_DIST_PID"                # this process's rank
ENV_LOCAL = "REPRO_DIST_LOCAL_DEVICES"    # forced-host devices per process


def initialize_from_env() -> Optional[int]:
    """Join the distributed runtime described by the REPRO_DIST_* env.

    No-op (returns None) when ``REPRO_DIST_COORD`` is unset, so worker
    entry points can call this unconditionally and still run
    single-process.  Returns the process id after
    ``jax.distributed.initialize``.
    """
    coord = os.environ.get(ENV_COORD)
    if coord is None:
        return None
    nproc = int(os.environ[ENV_NPROC])
    pid = int(os.environ[ENV_PID])
    local = int(os.environ.get(ENV_LOCAL, "1"))
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={local}"
        ).strip()

    import jax

    # cross-process CPU collectives ride on gloo; leave the default in
    # place on jaxlibs that pick the implementation themselves
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:  # pragma: no cover - jaxlib without the option
        pass
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=nproc, process_id=pid)
    return pid


def free_port() -> int:
    """An OS-assigned free TCP port (release-then-reuse: fine for a
    localhost coordinator started immediately after)."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def spawn_local(argv: Sequence[str], n_procs: int = 2,
                local_devices: int = 1, coordinator: Optional[str] = None,
                timeout: float = 1200.0,
                env: Optional[dict] = None) -> List[subprocess.CompletedProcess]:
    """Run ``n_procs`` copies of ``[sys.executable, *argv]`` as one
    jax.distributed job on this host.

    Each copy gets the REPRO_DIST_* env pointing at a shared localhost
    coordinator (process 0).  Blocks until every worker exits and returns
    their `CompletedProcess` results (stdout/stderr captured, text mode);
    the caller asserts on return codes and parses whatever the workers
    printed.  ``timeout`` bounds the whole job: past it every worker is
    killed and `subprocess.TimeoutExpired` raised.
    """
    coord = coordinator or f"127.0.0.1:{free_port()}"
    base = dict(os.environ if env is None else env)
    procs = []
    for pid in range(n_procs):
        e = dict(base)
        e.update({ENV_COORD: coord, ENV_NPROC: str(n_procs),
                  ENV_PID: str(pid), ENV_LOCAL: str(local_devices)})
        procs.append(subprocess.Popen(
            [sys.executable, *argv], env=e, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True))
    deadline = time.monotonic() + timeout
    out = []
    for p in procs:
        try:
            stdout, stderr = p.communicate(
                timeout=max(0.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
                q.wait()
            raise
        out.append(subprocess.CompletedProcess(p.args, p.returncode,
                                               stdout, stderr))
    return out
