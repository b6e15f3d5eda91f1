"""Job deployment: launch a multi-process training job.

TPU-native analogue of the reference's experimental ``job_deployment.py``
(SURVEY.md §2.1 [MED]: SSH-based submission of a training job to a remote
Spark cluster).  Here a "job" is one command run as N cooperating
``jax.distributed`` processes:

* ``launch_local`` — N processes on this host, every one pinned to the
  CPU backend (``JAX_PLATFORMS=cpu``): the substrate for multi-host
  integration tests and the direct analogue of the reference testing via
  Spark ``local[N]``.  It does not divide local accelerators among the
  children — a chip belongs to one process — so it never lets them see
  one.
* ``TPUPodJob`` — the command set a real TPU pod launch needs (one
  process per host via ``gcloud compute tpus tpu-vm ssh --worker=all``).
  With no network egress in this environment it only *builds* the
  commands (``dry_run=True``); running them requires a real pod.

Processes find each other through the ``JAX_COORDINATOR_ADDRESS`` /
``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID`` environment variables that
``distkeras_tpu.mesh.initialize_cluster`` reads.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import os
import shlex
import socket
import subprocess
import sys
from typing import Mapping, Sequence


@dataclasses.dataclass
class ProcessResult:
    process_id: int
    returncode: int
    stdout: str
    stderr: str


@dataclasses.dataclass
class JobSpec:
    """One multi-process job: ``argv`` is run once per process with the
    coordination env vars injected."""

    argv: Sequence[str]
    num_processes: int = 1
    env: Mapping[str, str] = dataclasses.field(default_factory=dict)
    cwd: str | None = None
    timeout_s: float = 900.0


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def launch_local(spec: JobSpec, check: bool = True
                 ) -> list[ProcessResult]:
    """Run ``spec.argv`` as ``num_processes`` local cooperating CPU
    processes.

    Every child gets ``JAX_PLATFORMS=cpu`` (over ``spec.env`` too): the
    caller may already hold this host's chip, N children would each try
    to open every chip, and there is no device slicing here — so local
    multi-process jobs run on the CPU backend, each child sizing its own
    virtual mesh through ``XLA_FLAGS`` in ``spec.env``.

    Returns per-process results (ordered by process id).  With ``check``,
    raises ``RuntimeError`` carrying every process's output if any exits
    nonzero — the whole job is one unit, like a Spark stage.
    """
    coord = f"127.0.0.1:{free_port()}"
    procs = []
    for i in range(spec.num_processes):
        env = {**os.environ, **spec.env,
               "JAX_PLATFORMS": "cpu",
               "JAX_COORDINATOR_ADDRESS": coord,
               "JAX_NUM_PROCESSES": str(spec.num_processes),
               "JAX_PROCESS_ID": str(i)}
        procs.append(subprocess.Popen(
            list(spec.argv), env=env, cwd=spec.cwd,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    # Drain every process concurrently: a sequential communicate() loop
    # deadlocks the job the moment a not-yet-reaped process fills its
    # ~64KiB pipe buffer while its peers block on a collective.
    results = []
    try:
        with concurrent.futures.ThreadPoolExecutor(
                max_workers=spec.num_processes) as pool:
            futs = [pool.submit(p.communicate, timeout=spec.timeout_s)
                    for p in procs]
            for i, (p, f) in enumerate(zip(procs, futs)):
                out, err = f.result()
                results.append(ProcessResult(i, p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    if check and any(r.returncode for r in results):
        detail = "\n".join(
            f"--- process {r.process_id} (rc={r.returncode}) ---\n"
            f"{r.stdout}\n{r.stderr}" for r in results)
        raise RuntimeError(f"local job failed:\n{detail}")
    return results


def run_multiprocess(script: str, num_processes: int,
                     args: Sequence[str] = (),
                     env: Mapping[str, str] | None = None,
                     timeout_s: float = 900.0) -> list[ProcessResult]:
    """Convenience wrapper: run a Python script as an N-process job with
    this interpreter."""
    spec = JobSpec(argv=[sys.executable, script, *args],
                   num_processes=num_processes, env=env or {},
                   timeout_s=timeout_s)
    return launch_local(spec)


@dataclasses.dataclass
class TPUPodJob:
    """Builds the gcloud command to run one process per pod host.

    ``jax.distributed.initialize`` auto-detects coordinator/process-id on
    TPU VMs, so the remote command needs no env injection.
    """

    tpu_name: str
    zone: str
    command: Sequence[str]
    project: str | None = None

    def build_command(self) -> list[str]:
        remote = " ".join(shlex.quote(c) for c in self.command)
        cmd = ["gcloud", "compute", "tpus", "tpu-vm", "ssh",
               self.tpu_name, f"--zone={self.zone}", "--worker=all",
               f"--command={remote}"]
        if self.project:
            cmd.insert(1, f"--project={self.project}")
        return cmd

    def submit(self, dry_run: bool = True):
        cmd = self.build_command()
        if dry_run:
            return cmd
        import shutil

        if shutil.which("gcloud") is None:
            raise RuntimeError(
                "gcloud not available (no network egress in this "
                "environment); use submit(dry_run=True) to inspect the "
                "command and run it from a workstation with access")
        return subprocess.run(cmd, check=True)
