"""No fallback that hides the device on the `backend="tpu"` path (ISSUE 22).

A chip that cannot be opened, a mesh that cannot be built and a program
the compiler refuses all stop the run with their own error; injected
faults and runtime errors heal exactly as before (the resilience suite
keeps its meaning). One process per chip: the launcher and the SC never
initialize a jax backend, and a second device-engine SPU is refused.
"""

from __future__ import annotations

import asyncio
import subprocess
import sys

import pytest

from fluvio_tpu.models import lookup
from fluvio_tpu.protocol.record import Record
from fluvio_tpu.resilience import faults
from fluvio_tpu.resilience.policy import (
    DETERMINISTIC,
    TRANSIENT,
    classify,
    is_program_fault,
)
from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig
from fluvio_tpu.smartengine.engine import EngineError
from fluvio_tpu.smartmodule import SmartModuleInput
from fluvio_tpu.telemetry import TELEMETRY

SPECS = [("regex-filter", {"regex": "fluvio"}), ("json-map", {"field": "name"})]


def _builder(backend, mesh=0):
    b = SmartEngine(backend=backend, mesh_devices=mesh).builder()
    for name, params in SPECS:
        b.add_smart_module(SmartModuleConfig(params=params), lookup(name))
    return b


def _input(n=64):
    records = [
        Record(value=f'{{"name":"{"fluvio" if i % 2 else "kafka"}-{i}"}}'.encode())
        for i in range(n)
    ]
    for i, r in enumerate(records):
        r.offset_delta = i
    return SmartModuleInput.from_records(records, 0, 1000)


class _Xla(Exception):
    """Stand-in with jaxlib's runtime-error class name."""


_Xla.__name__ = "XlaRuntimeError"


@pytest.mark.parametrize(
    "exc,program",
    [
        (NotImplementedError("Only 2D gather is supported"), True),
        (TypeError("unsupported operand"), True),
        (_Xla("INTERNAL: Mosaic failed to compile TPU kernel"), True),
        (_Xla("RESOURCE_EXHAUSTED: XLA:TPU compile permanent error"), True),
        (_Xla("INTERNAL: device halted"), False),
        (_Xla("RESOURCE_EXHAUSTED: out of memory allocating 1GB"), False),
        (RuntimeError("decode mismatch"), False),
        (faults.InjectedFault("dispatch", transient=False), False),
        (faults.InjectedFault("device"), False),
        (OSError("link reset"), False),
    ],
    ids=[
        "notimplemented", "typeerror", "mosaic-compile", "xla-compile-oom",
        "xla-internal-runtime", "xla-oom-runtime", "runtime", "injected-det",
        "injected-transient", "oserror",
    ],
)
def test_program_fault_classifier(exc, program):
    assert is_program_fault(exc) is program
    if program:
        # never retried as device weather (INTERNAL used to read transient)
        assert classify(exc) == DETERMINISTIC


def test_transient_markers_still_transient():
    assert classify(_Xla("INTERNAL: device halted")) == TRANSIENT
    assert classify(faults.InjectedFault("device")) == TRANSIENT


@pytest.mark.parametrize("seam", ["dispatch", "fetch"])
def test_lowering_error_raises_through_process(monkeypatch, seam):
    """backend="tpu": what only lowering raises is never answered by a
    heal, a `fused-error` spill or the interpreter."""
    from fluvio_tpu.smartengine.tpu.executor import TpuChainExecutor

    chain = _builder("tpu").initialize()
    target = "_dispatch" if seam == "dispatch" else "_fetch"

    def refuse(self, *a, **k):
        raise NotImplementedError(
            "Unimplemented primitive in Pallas TPU lowering: dynamic_slice"
        )

    monkeypatch.setattr(TpuChainExecutor, target, refuse)
    c0 = TELEMETRY.snapshot()["counters"]
    p0 = TELEMETRY.path_records().get("interpreter", 0)
    with pytest.raises(NotImplementedError, match="dynamic_slice"):
        chain.process(_input())
    c1 = TELEMETRY.snapshot()["counters"]
    assert c1["heals"] == c0["heals"]
    assert c1["spills"].get("fused-error", 0) == c0["spills"].get("fused-error", 0)
    assert TELEMETRY.path_records().get("interpreter", 0) == p0
    assert chain.breaker.state == "closed"


@pytest.mark.parametrize(
    "point,kind",
    [("dispatch", "transient"), ("device", "transient"),
     ("dispatch", "deterministic")],
    ids=["dispatch-transient", "device-transient", "dispatch-deterministic"],
)
def test_injected_faults_still_heal(monkeypatch, point, kind):
    """The fault seams keep their meaning: transient faults retry, a
    deterministic one demotes the batch to the interpreter — output
    byte-equal to the python backend either way."""
    monkeypatch.setenv("FLUVIO_RETRY_BASE_MS", "0")
    chain = _builder("tpu").initialize()
    ref = _builder("python").initialize().process(_input())
    faults.FAULTS.inject(point, first=1, exc=kind)
    try:
        out = chain.process(_input())
    finally:
        faults.FAULTS.clear()
    assert out.error is None
    assert [r.value for r in out.successes] == [r.value for r in ref.successes]


def test_unopenable_device_is_an_engine_error_at_initialize(monkeypatch):
    import jax

    def no_chip(*a, **k):
        raise RuntimeError("Unable to initialize backend 'tpu': device busy")

    monkeypatch.setattr(jax, "devices", no_chip)
    with pytest.raises(EngineError, match="cannot be opened.*device busy"):
        _builder("tpu").initialize()


def test_failed_mesh_is_an_engine_error_under_tpu_a_warning_under_auto(caplog):
    with pytest.raises(EngineError, match="sharded engine mode unavailable"):
        _builder("tpu", mesh=64).initialize()
    chain = _builder("auto", mesh=64).initialize()
    assert chain.tpu_chain is not None and chain.tpu_chain._sharded is None
    assert "sharded engine mode unavailable" in caplog.text


def test_missing_executor_module_raises_under_tpu(monkeypatch):
    monkeypatch.setitem(sys.modules, "fluvio_tpu.smartengine.tpu.executor", None)
    with pytest.raises(ImportError):
        _builder("tpu").initialize()
    chain = _builder("auto").initialize()  # auto serves from a host engine
    assert chain.tpu_chain is None


# ---------------------------------------------------------------------------
# one form for the up-link (ISSUE 33)
# ---------------------------------------------------------------------------


def test_no_symbol_of_the_deleted_link_compression_is_left():
    """The glz up-link went whole in PR 33 (compressor, device inflate,
    compress-ahead workers, sharded and preflight mirrors): a mirror
    deleted by half fails here, in tier-1, and not on the chip."""
    import pathlib
    import re

    import fluvio_tpu

    gone = re.compile(r"\b(compress_link|decompress_device|_precompress\w*)\b")
    root = pathlib.Path(fluvio_tpu.__file__).parent
    left = [
        f"{path.relative_to(root)}:{n}: {line.strip()}"
        for path in sorted(root.rglob("*"))
        if path.suffix in (".py", ".cpp")
        for n, line in enumerate(path.read_text().splitlines(), 1)
        if gone.search(line)
    ]
    assert not left, left


# ---------------------------------------------------------------------------
# one process per chip
# ---------------------------------------------------------------------------


def test_launcher_and_sc_never_touch_jax():
    """The local-cluster launcher, the SC and the process host import
    (let alone initialize) no jax: only an SPU child may hold the chip."""
    code = (
        "import sys\n"
        "import fluvio_tpu.cluster.local, fluvio_tpu.sc.start, fluvio_tpu.run\n"
        "import fluvio_tpu.cli\n"
        "assert 'jax' not in sys.modules, 'jax imported by the launcher/SC'\n"
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_local_cluster_refuses_a_second_device_spu(tmp_path):
    from fluvio_tpu.cluster.local import (
        LocalClusterError,
        LocalConfig,
        LocalInstaller,
    )

    installer = LocalInstaller(
        LocalConfig(data_dir=str(tmp_path), spus=2, engine="tpu",
                    skip_checks=True)
    )
    with pytest.raises(LocalClusterError, match="exactly one SPU"):
        asyncio.new_event_loop().run_until_complete(installer.install())
    assert installer.processes == [], "nothing may be spawned before refusing"


def test_spu_start_fails_loudly_when_the_chip_cannot_be_opened(
    monkeypatch, tmp_path
):
    import jax

    from fluvio_tpu.spu import SpuConfig, SpuServer
    from fluvio_tpu.storage.config import ReplicaConfig

    def held(*a, **k):
        raise RuntimeError("The TPU is already in use by another process")

    monkeypatch.setattr(jax, "devices", held)
    config = SpuConfig(
        id=9100, public_addr="127.0.0.1:0", log_base_dir=str(tmp_path),
        replication=ReplicaConfig(base_dir=str(tmp_path)),
    )
    config.smart_engine.backend = "tpu"
    server = SpuServer(config)
    with pytest.raises(EngineError, match="already in use"):
        asyncio.new_event_loop().run_until_complete(server.start())
