"""SmartEngine, chain builder, and chain instance.

Capability parity: fluvio-smartengine/src/engine/wasmtime/engine.rs —
`SmartEngine::new` (engine.rs:31), `SmartModuleChainBuilder::initialize`
(engine.rs:65-91: compile each module, detect transform kind, run init),
`SmartModuleChainInstance::process` (engine.rs:135-185: pipe input through
instances, preserve base offset/timestamp, short-circuit on first error,
meter each call) and `look_back` (engine.rs:187-218).

Backend selection replaces the reference's single wasmtime runtime:

- ``python``  — per-record interpreter (semantics reference)
- ``tpu``     — fused JAX/XLA chain over the batched record buffer;
                requires every module in the chain to carry a DSL program
- ``auto``    — tpu when the whole chain is lowerable, else python
"""

from __future__ import annotations

import asyncio
import logging
import time

from dataclasses import dataclass, field
from typing import Awaitable, Callable, List, Optional

from fluvio_tpu.smartmodule.sdk import SmartModuleDef, load_source
from fluvio_tpu.smartmodule.types import (
    SmartModuleInput,
    SmartModuleKind,
    SmartModuleOutput,
    SmartModuleRecord,
)
from fluvio_tpu.smartengine.config import Lookback, SmartModuleConfig
from fluvio_tpu.smartengine.metrics import SmartModuleChainMetrics
from fluvio_tpu.smartengine.python_backend import PythonInstance

logger = logging.getLogger(__name__)

DEFAULT_STORE_MAX_MEMORY = 1 << 30  # 1 GB input bound, parity: engine.rs:24


class EngineError(Exception):
    pass


def touch_device() -> None:
    """Open the jax backend (``backend="tpu"``: once at SPU start and
    at every chain build): a chip that cannot be opened is an
    `EngineError` here, not a failure of the first batch that the fused
    path's catch-all would answer with an interpreter re-run."""
    import jax

    try:
        jax.block_until_ready(jax.device_put(0, jax.devices()[0]))
    except Exception as e:  # noqa: BLE001 — backend init boundary
        raise EngineError(
            f"backend='tpu': the jax device backend cannot be opened: {e}"
        ) from e


class StoreMemoryExceeded(EngineError):
    """Input slab exceeds the engine memory bound (parity: limiter.rs)."""

    def __init__(self, requested: int, maximum: int):
        super().__init__(
            f"SmartModule input of {requested} bytes exceeds engine memory "
            f"limit of {maximum} bytes"
        )
        self.requested = requested
        self.maximum = maximum


class SmartModuleChainInitError(EngineError):
    """A module's init hook failed during chain build (parity: engine.rs)."""


@dataclass
class SmartEngine:
    """Engine factory/config. Cheap to clone; owns no per-chain state."""

    backend: str = "python"  # python | tpu | auto
    store_max_memory: int = DEFAULT_STORE_MAX_MEMORY
    # multi-device engine mode: shard chains over an n-device record
    # mesh via shard_map (0/1 = single device)
    mesh_devices: int = 0
    # wall-clock budget per Python-hook call, ms (0 = unmetered; the
    # fuel analog — DSL programs are bounded by construction, arbitrary
    # hooks are not; see smartengine/metering.py). The SPU enables this
    # by default so a hostile module cannot wedge the broker.
    hook_budget_ms: int = 0

    def builder(self) -> "SmartModuleChainBuilder":
        return SmartModuleChainBuilder(engine=self)


def _init_instances(entries, engine: SmartEngine) -> List[PythonInstance]:
    """One interpreter instance per (module, config), its init hook
    run: what a chain build and a further stream of a built chain both
    start from."""
    from fluvio_tpu.smartengine.metering import run_metered

    instances = []
    for module, config in entries:
        inst = PythonInstance(module, config)
        try:
            # init is user code too: a looping init must become a
            # typed chain-init error, not a wedged chain build
            run_metered(
                inst.call_init,
                engine.hook_budget_ms,
                module.name,
                key=getattr(module, "meter_key", ""),
            )
        except Exception as e:  # noqa: BLE001 — user code boundary
            raise SmartModuleChainInitError(
                f"init failed for SmartModule {module.name!r}: {e}"
            ) from e
        instances.append(inst)
    return instances


@dataclass
class _ChainEntry:
    module: SmartModuleDef
    config: SmartModuleConfig


@dataclass
class SmartModuleChainBuilder:
    engine: SmartEngine = field(default_factory=SmartEngine)
    entries: List[_ChainEntry] = field(default_factory=list)

    def add_smart_module(
        self,
        config: SmartModuleConfig,
        module: SmartModuleDef | str | bytes,
        name: str = "adhoc",
    ) -> "SmartModuleChainBuilder":
        if not isinstance(module, SmartModuleDef):
            module = load_source(module, name=name)
        self.entries.append(_ChainEntry(module=module, config=config))
        return self

    def __len__(self) -> int:
        return len(self.entries)

    def initialize(self, engine: Optional[SmartEngine] = None) -> "SmartModuleChainInstance":
        engine = engine or self.engine
        instances = _init_instances(
            [(e.module, e.config) for e in self.entries], engine
        )

        backend = engine.backend
        tpu_chain = None
        native_chain = None
        # an empty chain is decode-and-passthrough on every backend
        # (parity: engine.rs:180-184); nothing to lower
        if backend in ("tpu", "auto") and self.entries:
            try:
                from fluvio_tpu.smartengine.tpu.executor import TpuChainExecutor
            except ImportError:
                # `auto` without jax serves from the host engines; an
                # explicit device backend has nothing to fall back to
                if backend == "tpu":
                    raise
                TpuChainExecutor = None
            if backend == "tpu":
                touch_device()
            if TpuChainExecutor is not None:
                tpu_chain = TpuChainExecutor.try_build(
                    [(e.module, e.config) for e in self.entries]
                )
            if tpu_chain is not None:
                tpu_chain.attach(instances)
                if engine.mesh_devices and engine.mesh_devices > 1:
                    try:
                        tpu_chain.enable_sharded(engine.mesh_devices)
                    except ValueError as e:
                        if backend == "tpu":
                            raise EngineError(
                                f"backend='tpu' with mesh_devices="
                                f"{engine.mesh_devices}: sharded engine "
                                f"mode unavailable: {e}"
                            ) from e
                        # auto: not enough devices / unshardable chain —
                        # stay on the single-device executor
                        logger.warning("sharded engine mode unavailable: %s", e)
            if tpu_chain is None and backend == "tpu":
                raise EngineError(
                    "backend='tpu' requires every module in the chain to "
                    "carry a DSL program"
                )
        # native (C++) per-record engine: the compiled host path — auto
        # falls back to it when the TPU path is unavailable
        if backend in ("native", "auto") and self.entries and tpu_chain is None:
            from fluvio_tpu.smartengine.native_backend import NativeChainExecutor

            native_chain = NativeChainExecutor.try_build(
                [(e.module, e.config) for e in self.entries]
            )
            if native_chain is not None:
                native_chain.attach(instances)
            elif backend == "native":
                raise EngineError(
                    "backend='native' requires every module in the chain to "
                    "carry a DSL program (or no C++ toolchain is available)"
                )
        # replayable chain identity for the dead-letter quarantine: the
        # module names/kinds/params (and aggregate seeds) are enough to
        # rebuild the chain from the local store or the models registry
        import base64

        chain_spec = []
        for entry in self.entries:
            spec = {
                "name": entry.module.name,
                "kind": entry.module.transform_kind().value,
                "params": dict(entry.config.params or {}),
            }
            if entry.config.initial_data:
                spec["initial"] = base64.b64encode(
                    bytes(entry.config.initial_data)
                ).decode("ascii")
            chain_spec.append(spec)
        return SmartModuleChainInstance(
            engine=engine,
            instances=instances,
            tpu_chain=tpu_chain,
            native_chain=native_chain,
            chain_spec=chain_spec,
        )


class SmartModuleChainInstance:
    """An initialized chain; processes inputs one slab at a time."""

    def __init__(
        self,
        engine: SmartEngine,
        instances: List[PythonInstance],
        tpu_chain=None,
        native_chain=None,
        chain_spec=None,
    ):
        self.engine = engine
        self.instances = instances
        self.tpu_chain = tpu_chain
        self.native_chain = native_chain
        self.chain_spec = chain_spec or []
        # chain identity for telemetry samples: the executor's compact
        # signature when a fused path exists (so interpreter reruns of
        # the SAME chain land in the SAME per-chain latency family the
        # SLO engine windows), else the module-kind composition
        self.chain_label = (
            tpu_chain._chain_sig
            if tpu_chain is not None
            else "+".join(i.kind.value for i in instances) or "empty"
        )
        # set when a fuel trap abandoned a hook thread (metering.py):
        # the chain fails fast with this error instead of re-entering
        # user code whose previous invocation is still running
        self._poisoned = None
        # the chain this one is a stream of (`open_stream`), which
        # shares its modules: a poisoning reaches it too
        self._origin: Optional["SmartModuleChainInstance"] = None
        # per-chain circuit breaker (resilience/policy.py): M fused
        # failures in a window demote the chain to the interpreter path
        # outright; probe batches re-promote it after the cooldown. Only
        # chains with a fused path have anything to break.
        self.breaker = None
        self._spill_retry = None
        if tpu_chain is not None:
            from fluvio_tpu.resilience.policy import CircuitBreaker, RetryPolicy

            self.breaker = CircuitBreaker()
            self._spill_retry = RetryPolicy()

    def __len__(self) -> int:
        return len(self.instances)

    def open_stream(self) -> "SmartModuleChainInstance":
        """A further consumer stream of this chain (fused chains only):
        the compiled executor is shared, everything a stream advances
        is new: interpreter instances (each module's init hook runs
        again, as a per-stream instantiate does), the executor's
        `StreamState` from the chain spec's seed, breaker and retry
        budget. The SPU's stream-chain cache hands these out for a
        stateful chain, so that a stream's open costs no re-trace and
        no executable load."""
        instances = _init_instances(
            [(i.module, i.config) for i in self.instances], self.engine
        )
        tpu_chain = self.tpu_chain.open_stream()
        tpu_chain.attach(instances)
        stream = SmartModuleChainInstance(
            engine=self.engine,
            instances=instances,
            tpu_chain=tpu_chain,
            chain_spec=self.chain_spec,
        )
        stream._origin = self
        return stream

    def _poison(self, error) -> None:
        """A fuel trap left user code of this chain's modules running:
        the chain, and the chain it is a stream of, fail fast from here
        on."""
        self._poisoned = error
        if self._origin is not None:
            self._origin._poisoned = error

    @property
    def backend_in_use(self) -> str:
        if self.tpu_chain is not None:
            return "tpu"
        if self.native_chain is not None:
            return "native"
        return "python"

    def process(
        self,
        inp: SmartModuleInput,
        metrics: Optional[SmartModuleChainMetrics] = None,
    ) -> SmartModuleOutput:
        metrics = metrics if metrics is not None else SmartModuleChainMetrics()
        raw_len = inp.byte_size()
        if raw_len > self.engine.store_max_memory:
            raise StoreMemoryExceeded(raw_len, self.engine.store_max_memory)
        metrics.add_bytes_in(raw_len)

        if self.tpu_chain is not None:
            from fluvio_tpu.smartengine.tpu.executor import TpuSpill
            from fluvio_tpu.telemetry import TELEMETRY

            fused_error = None
            breaker_failure = False
            if self.breaker is not None and not self.breaker.allow_fused():
                # breaker open: no fused attempt at all — the stream
                # runs interpreted (through the SAME rerun ladder as a
                # spill: spill_rerun seam, transient retry, quarantine)
                # until the cooldown half-opens it
                TELEMETRY.add_breaker_short_circuit()
                fused_error = RuntimeError("fused path skipped: breaker open")
                return self._spill_rerun(inp, metrics, fused_error)
            try:
                output = self.tpu_chain.process(inp, metrics)
            except TpuSpill as e:
                # device detected a transform error (or exhausted fan-out
                # capacity): the interpreting python instances re-run the
                # batch for exact first-error semantics (device carries
                # were restored, and are re-mirrored from the instances
                # after the rerun)
                # NOT a breaker failure: spills are expected, often
                # data-dependent demotions (a record that errors under
                # exact semantics, a too-wide batch) — device health is
                # what the breaker guards, and tripping it on data would
                # demote CLEAN batches to interpreter speed
                TELEMETRY.add_spill(getattr(e, "reason", "transform-error"))
                fused_error = e
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                from fluvio_tpu.resilience.policy import is_program_fault

                if is_program_fault(e):
                    # a lowering/compile error is the program's fault,
                    # not the device's: the interpreter must not answer
                    # for a kernel the compiler refused
                    raise
                # non-spill fused failure (deterministic fault, or a
                # transient one that exhausted its retry budget): same
                # demotion as a spill — the executor restored the carry
                # snapshot before re-raising, so the rerun is exact
                logger.warning(
                    "fused path failed (%s: %s); interpreter re-run",
                    type(e).__name__, e,
                )
                TELEMETRY.add_spill("fused-error")
                fused_error = e
                breaker_failure = True
            if fused_error is None:
                if self.breaker is not None:
                    self.breaker.record_success()
                metrics.add_records_out(len(output.successes))
                return output
            if self.breaker is not None and breaker_failure:
                self.breaker.record_failure()
            return self._spill_rerun(inp, metrics, fused_error)

        if self.native_chain is not None:
            output = self.native_chain.process(inp, metrics)
            metrics.add_records_out(len(output.successes))
            return output

        if not self.instances:
            # Empty chain: decode-and-passthrough (parity: engine.rs:180-184)
            return SmartModuleOutput.new(inp.into_records())

        return self._process_instances(inp, metrics)

    def _spill_rerun(
        self,
        inp: SmartModuleInput,
        metrics: SmartModuleChainMetrics,
        fused_error: BaseException,
    ) -> SmartModuleOutput:
        """The interpreter rerun ladder every fused-path demotion takes
        (spill, non-spill fused failure, open breaker): rerun with
        bounded transient retry — a one-off host failure must not
        condemn the batch as poison — then quarantine. A snapshot of
        every instance's state (`PythonInstance.state_snapshot`) makes
        every attempt start from the same aggregates, and a quarantined
        batch contributes nothing to them."""
        from fluvio_tpu.telemetry import TELEMETRY

        policy = self._spill_retry
        snapshot = [i.state_snapshot() for i in self.instances]
        attempt = 0
        while True:
            try:
                return self._process_instances(inp, metrics, spilled=True)
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as interp_error:
                # every exit from a failed rerun restores the snapshot:
                # a half-advanced accumulator must leak neither into the
                # next attempt nor — via the quarantine's state re-sync
                # — into the device carries of a batch the stream
                # reports as empty
                self._restore_instances(snapshot)
                if policy.should_retry(interp_error, attempt):
                    TELEMETRY.add_retry("spill_rerun")
                    policy.sleep(attempt)
                    attempt += 1
                    continue
                # poison: BOTH execution paths failed — dead-letter the
                # batch and advance the stream instead of crashing it
                return self._quarantine(inp, fused_error, interp_error)

    def _restore_instances(self, snapshot) -> None:
        """Roll every instance's state back to a pre-rerun snapshot."""
        for inst, snap in zip(self.instances, snapshot):
            inst.state_restore(snap)

    def _quarantine(
        self,
        inp: SmartModuleInput,
        fused_error: BaseException,
        interp_error: BaseException,
    ) -> SmartModuleOutput:
        """Poison-batch handling: both execution paths failed.

        The batch is dumped — replayable chain spec + records + both
        errors — into the bounded dead-letter directory, the counter
        ticks, and an EMPTY output (no error) lets the stream advance.
        The python instances (already rolled back to their pre-batch
        snapshot by the caller) are re-asserted as the authoritative
        state, so a quarantined batch contributes NOTHING to aggregate
        carries — replaying its dead-letter entry later cannot
        double-count."""
        from fluvio_tpu.resilience.deadletter import quarantine_batch
        from fluvio_tpu.telemetry import TELEMETRY

        path = quarantine_batch(
            self.chain_spec, inp, fused_error, interp_error
        )
        TELEMETRY.add_quarantine()
        logger.error(
            "poison batch quarantined to %s (fused: %s; interpreter: %s)",
            path or "<dead-letter dir unwritable>", fused_error, interp_error,
        )
        if self.tpu_chain is not None:
            self.tpu_chain.sync_state_from(self.instances)
        return SmartModuleOutput()

    def _process_instances(
        self,
        inp: SmartModuleInput,
        metrics: SmartModuleChainMetrics,
        spilled: bool = False,
    ) -> SmartModuleOutput:
        """Interpreting per-instance pipeline (exact reference semantics).

        Python hooks run under the engine's wall-clock fuel budget
        (`hook_budget_ms`): exhaustion becomes a transform error — the
        same surface a wasm fuel trap takes in the reference
        (state.rs:40-55) — so the stream gets a typed error response and
        the broker stays live instead of spinning forever.

        Telemetry: the whole pass records as ONE interpreter-path batch
        span (one clock pair — no per-record work); a fused-path spill
        rerun (``spilled=True``) additionally books its wall time under
        the ``spill`` phase so fused-vs-interpreter time is comparable
        per batch."""
        from fluvio_tpu.telemetry import TELEMETRY
        from fluvio_tpu.resilience import faults

        if spilled:
            # the spill-rerun seam: a batch whose interpreter re-run
            # also fails is poison — process() quarantines it
            faults.maybe_fire("spill_rerun")
        span = TELEMETRY.begin_batch(path="interpreter", chain=self.chain_label)
        from fluvio_tpu.smartengine.metering import (
            SmartModuleFuelError,
            run_metered,
            scale_budget,
        )
        from fluvio_tpu.smartmodule.types import (
            SmartModuleTransformRuntimeError,
        )

        base_offset = inp.base_offset
        base_timestamp = inp.base_timestamp
        n_rec = len(inp.records) if inp.records is not None else inp.raw_count
        if self._poisoned is not None:
            # an earlier fuel trap left this chain's hook thread alive
            # and possibly mid-mutation: never re-enter it. The rejected
            # batch still records: an error storm on a poisoned chain
            # must stay visible in interpreter batch counts
            out = SmartModuleOutput()
            out.error = self._poisoned
            TELEMETRY.end_batch(span, records=n_rec)
            return out
        budget = scale_budget(self.engine.hook_budget_ms, n_rec)
        next_input = inp
        output = SmartModuleOutput()
        for i, instance in enumerate(self.instances):
            try:
                output = run_metered(
                    lambda: instance.process(next_input, metrics),
                    budget,
                    getattr(instance.module, "name", "smartmodule"),
                    key=getattr(instance.module, "meter_key", ""),
                )
            except SmartModuleFuelError as e:
                output = SmartModuleOutput()
                output.error = SmartModuleTransformRuntimeError(
                    hint=str(e),
                    offset=base_offset,
                    kind=instance.kind,
                )
                # abandoned: the hook thread is still running. Stateful
                # (aggregate) instances poison on ANY trap: the injected
                # exception lands at an arbitrary bytecode boundary, so
                # the accumulator may be half-mutated even when the hook
                # unwound cleanly.
                if e.abandoned or instance.kind is SmartModuleKind.AGGREGATE:
                    self._poison(output.error)
                break
            if output.error is not None:
                # stop processing, return partial output (engine.rs:159-161)
                break
            if i + 1 < len(self.instances):
                next_input = SmartModuleInput.from_records(
                    output.successes,
                    base_offset=base_offset,
                    base_timestamp=base_timestamp,
                )
        if self.tpu_chain is not None:
            # a spill rerun advanced the python accumulators; mirror back
            self.tpu_chain.sync_state_from(self.instances)
        if output.error is None:
            metrics.add_records_out(len(output.successes))
        if span is not None:
            if spilled:
                span.add("spill", time.perf_counter() - span.t0)
            TELEMETRY.end_batch(span, records=n_rec)
        return output

    async def look_back(
        self,
        read_fn: Callable[[Lookback], Awaitable[List[SmartModuleRecord]]],
        metrics: Optional[SmartModuleChainMetrics] = None,
    ) -> None:
        """Feed recent records to each module exporting look_back.

        ``read_fn`` receives the module's Lookback config and returns the
        records to replay (parity: engine.rs:187-218).
        """
        from fluvio_tpu.smartengine.metering import (
            SmartModuleFuelError,
            run_metered,
            scale_budget,
        )

        for instance in self.instances:
            if not instance.module.has_look_back():
                continue
            lookback = instance.config.lookback or Lookback.last_n(0)
            records = await read_fn(lookback)
            if metrics is not None:
                metrics.add_bytes_in(sum(len(r.value) for r in records))
            # look_back replays user code over stored records on the
            # broker: same fuel budget as process (error propagates as a
            # chain error to the stream that attached the module)
            try:
                # off the event loop: a looping look_back must stall only
                # this attach, never every broker connection
                await asyncio.to_thread(
                    run_metered,
                    lambda: instance.call_look_back(records),
                    scale_budget(self.engine.hook_budget_ms, len(records)),
                    getattr(instance.module, "name", "smartmodule"),
                    getattr(instance.module, "meter_key", ""),
                )
            except SmartModuleFuelError as e:
                if e.abandoned:
                    from fluvio_tpu.smartmodule.types import (
                        SmartModuleTransformRuntimeError,
                    )

                    self._poison(
                        SmartModuleTransformRuntimeError(
                            hint=str(e), kind=instance.kind
                        )
                    )
                raise
            # keep any device/native-side state in sync after host replay
            if self.tpu_chain is not None:
                self.tpu_chain.sync_state_from(self.instances)
            if self.native_chain is not None:
                self.native_chain.sync_state_from(self.instances)
