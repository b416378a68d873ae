"""Local cluster installer.

Capability parity: fluvio-cluster/src/start/local.rs:327-463 — spawn
``fluvio-run sc`` and per-SPU ``fluvio-run spu`` child processes, register
each SPU with the SC admin API, write the client profile, and record the
process state for delete/status. Here the children are
``python -m fluvio_tpu.run sc|spu`` and state lives in
``<data_dir>/cluster-state.json``.
"""

from __future__ import annotations

import asyncio
import json
import os
import subprocess
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import List, Optional

from fluvio_tpu.client import Fluvio
from fluvio_tpu.client.config import ConfigFile, FluvioClusterConfig, LOCAL_PROFILE

DEFAULT_DATA_DIR = "~/.fluvio-tpu/data"
STATE_FILE = "cluster-state.json"
BASE_SPU_ID = 5001


class LocalClusterError(Exception):
    pass


@dataclass
class LocalConfig:
    data_dir: str = DEFAULT_DATA_DIR
    spus: int = 1
    sc_public_port: int = 0  # 0 = ephemeral
    sc_private_port: int = 0
    engine: str = "auto"
    profile_name: str = LOCAL_PROFILE
    skip_checks: bool = False
    launch_timeout_s: float = 30.0
    env: dict = field(default_factory=dict)

    def resolved_data_dir(self) -> str:
        return str(Path(self.data_dir).expanduser())


def cluster_state_path(data_dir: str) -> str:
    return str(Path(data_dir).expanduser() / STATE_FILE)


def load_cluster_state(data_dir: str) -> Optional[dict]:
    path = cluster_state_path(data_dir)
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def save_cluster_state(data_dir: str, state: dict) -> None:
    path = cluster_state_path(data_dir)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(state, f, indent=2)


class LocalInstaller:
    """Bring up SC + N SPUs as child processes (start/local.rs:400)."""

    def __init__(self, config: LocalConfig):
        self.config = config
        self.data_dir = config.resolved_data_dir()
        self.processes: List[subprocess.Popen] = []

    async def install(self) -> dict:
        from fluvio_tpu.cluster.check import ClusterChecker

        if self.config.engine == "tpu" and self.config.spus > 1:
            # one process per chip: only ONE SPU child can hold the
            # device engine (a second one would die at start — refuse
            # before spawning anything). The launcher and the SC never
            # initialize a jax backend themselves.
            raise LocalClusterError(
                f"engine='tpu' with spus={self.config.spus}: a chip belongs "
                "to one process — a local cluster on one chip has exactly "
                "one SPU with the device engine"
            )
        if not self.config.skip_checks:
            ClusterChecker.local_preflight(self.data_dir).run_or_fail()
        os.makedirs(self.data_dir, exist_ok=True)

        sc_public, sc_private, sc_pid = self._launch_sc()
        state = {
            "sc_pid": sc_pid,
            "sc_public": sc_public,
            "sc_private": sc_private,
            "data_dir": self.data_dir,
            "spus": [],
        }
        save_cluster_state(self.data_dir, state)

        try:
            await self._provision_spus(state, sc_public, sc_private)
        except Exception:
            self.kill()
            raise

        self._write_profile(sc_public)
        save_cluster_state(self.data_dir, state)
        return state

    # -- process spawning ---------------------------------------------------

    def _spawn(self, args: List[str], log_name: str) -> subprocess.Popen:
        log_path = os.path.join(self.data_dir, log_name)
        log = open(log_path, "ab")
        env = dict(os.environ)
        env.update(self.config.env)
        proc = subprocess.Popen(
            [sys.executable, "-m", "fluvio_tpu.run", *args],
            stdout=log,
            stderr=subprocess.STDOUT,
            env=env,
            start_new_session=True,  # survive the installer's terminal
        )
        log.close()
        self.processes.append(proc)
        return proc

    def _wait_port_file(self, path: str, proc: subprocess.Popen, what: str) -> dict:
        deadline = time.monotonic() + self.config.launch_timeout_s
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise LocalClusterError(
                    f"{what} exited with {proc.returncode} during launch "
                    f"(log in {self.data_dir})"
                )
            if os.path.exists(path):
                with open(path) as f:
                    return json.load(f)
            time.sleep(0.05)
        raise LocalClusterError(f"{what} did not come up in time")

    def _launch_sc(self) -> tuple:
        port_file = os.path.join(self.data_dir, "sc.ports")
        if os.path.exists(port_file):
            os.remove(port_file)
        metadata_dir = os.path.join(self.data_dir, "metadata")
        proc = self._spawn(
            [
                "sc",
                "--public-addr",
                f"127.0.0.1:{self.config.sc_public_port}",
                "--private-addr",
                f"127.0.0.1:{self.config.sc_private_port}",
                "--metadata-dir",
                metadata_dir,
                "--port-file",
                port_file,
            ],
            "sc.log",
        )
        addrs = self._wait_port_file(port_file, proc, "SC")
        return addrs["public"], addrs["private"], proc.pid

    async def _provision_spus(
        self, state: dict, sc_public: str, sc_private: str
    ) -> None:
        """Register each SPU with the admin API, then spawn its process
        (start/local.rs:456 launch_spu_group + runtime/local/spu.rs:32)."""
        client = await Fluvio.connect(sc_public)
        try:
            admin = await client.admin()
            for i in range(self.config.spus):
                spu_id = BASE_SPU_ID + i
                port_file = os.path.join(self.data_dir, f"spu-{spu_id}.ports")
                if os.path.exists(port_file):
                    os.remove(port_file)
                log_dir = os.path.join(self.data_dir, f"spu-{spu_id}")
                proc = self._spawn(
                    [
                        "spu",
                        "-i",
                        str(spu_id),
                        "--sc-addr",
                        sc_private,
                        "--log-dir",
                        log_dir,
                        "--engine",
                        self.config.engine,
                        "--port-file",
                        port_file,
                    ],
                    f"spu-{spu_id}.log",
                )
                addrs = self._wait_port_file(port_file, proc, f"SPU {spu_id}")
                await admin.register_custom_spu(
                    spu_id, addrs["public"], addrs["private"]
                )
                state["spus"].append(
                    {
                        "id": spu_id,
                        "pid": proc.pid,
                        "public": addrs["public"],
                        "private": addrs["private"],
                    }
                )
            # wait until the SC reports every SPU online
            deadline = asyncio.get_running_loop().time() + self.config.launch_timeout_s
            while True:
                online = {
                    o.spec.id
                    for o in await admin.list("spu")
                    if o.status is not None and o.status.is_online()
                }
                if all(s["id"] in online for s in state["spus"]):
                    break
                if asyncio.get_running_loop().time() > deadline:
                    raise LocalClusterError(
                        f"SPUs never came online (online: {sorted(online)})"
                    )
                await asyncio.sleep(0.1)
            await admin.close()
        finally:
            await client.close()

    def _write_profile(self, sc_public: str) -> None:
        cf = ConfigFile.load()
        cf.config.add_cluster(
            self.config.profile_name, FluvioClusterConfig(endpoint=sc_public)
        )
        cf.save()

    def kill(self) -> None:
        for proc in self.processes:
            if proc.poll() is None:
                proc.terminate()
