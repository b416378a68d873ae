"""SPU public API service: produce / fetch / stream-fetch / offsets.

Capability parity: fluvio-spu/src/services/public/ — the per-connection
dispatch loop, `handle_produce_request` (produce_handler.rs:56,87,159),
`StreamFetchHandler` with its select loop and `send_back_records`
(stream_fetch.rs:39,229-326,340; zero-copy branch :443), offset fetch
(offset_request.rs) and consumer acks (offset_update.rs).
"""

from __future__ import annotations

import asyncio
import logging
import time
import weakref
from typing import Dict, List, Optional

from fluvio_tpu.protocol.api import (
    ApiVersionKey,
    ApiVersionsRequest,
    ApiVersionsResponse,
    ResponseMessage,
    decode_request_header,
)
from fluvio_tpu.protocol.codec import ByteWriter
from fluvio_tpu.protocol.error import ErrorCode, FluvioError
from fluvio_tpu.protocol.record import RecordSet
from fluvio_tpu.schema.spu import (
    FetchablePartitionResponse,
    FetchOffsetsRequest,
    FetchOffsetsResponse,
    FetchRequest,
    FetchResponse,
    Isolation,
    OffsetUpdateStatus,
    PartitionProduceResponse,
    ProduceRequest,
    ProduceResponse,
    SpuServerApiKey,
    StreamFetchRequest,
    StreamFetchResponse,
    TopicProduceResponse,
    UpdateOffsetsRequest,
    UpdateOffsetsResponse,
)
from fluvio_tpu.spu.context import GlobalContext
from fluvio_tpu.spu.replica import LeaderReplicaState
from fluvio_tpu.spu.smart_chain import (
    BatchProcessResult,
    PendingSlice,
    SmartModuleResolutionError,
    admission_chain_sig,
    admission_check,
    admission_note_warm,
    admission_require_warm,
    apply_chain,
    acquire_stream_chain,
    build_chain,
    chain_look_back,
    ensure_dedup_chain,
    process_batches,
    process_batches_per_record,
    tpu_dispatch,
    tpu_fetch,
    tpu_materialize,
    tpu_stage,
)
from fluvio_tpu.smartengine.engine import EngineError, SmartModuleChainInitError
from fluvio_tpu.smartengine.metering import SmartModuleFuelError
from fluvio_tpu.telemetry import TELEMETRY
from fluvio_tpu.telemetry import lag as lag_mod
from fluvio_tpu.telemetry.registry import tenant_label
from fluvio_tpu.telemetry.spans import timed
from fluvio_tpu.transport.service import FluvioService
from fluvio_tpu.transport.sink import ExclusiveSink, FluvioSink
from fluvio_tpu.transport.socket import FluvioSocket, SocketClosed
from fluvio_tpu.types import OffsetPublisher, StickyEvent

logger = logging.getLogger(__name__)

SPU_API_KEYS = [
    ApiVersionKey(SpuServerApiKey.API_VERSION, 0, 0),
    ApiVersionKey(SpuServerApiKey.PRODUCE, 0, ProduceRequest.MAX_API_VERSION),
    ApiVersionKey(SpuServerApiKey.FETCH, 0, FetchRequest.MAX_API_VERSION),
    ApiVersionKey(SpuServerApiKey.FETCH_OFFSETS, 0, 0),
    ApiVersionKey(SpuServerApiKey.STREAM_FETCH, 0, StreamFetchRequest.MAX_API_VERSION),
    ApiVersionKey(SpuServerApiKey.UPDATE_OFFSETS, 0, 0),
]


class ConnectionContext:
    """Per-connection state: push streams + their consumer-ack buses."""

    def __init__(self) -> None:
        self.next_stream_id = 1
        self.ack_publishers: Dict[int, OffsetPublisher] = {}
        self.stream_tasks: Dict[int, asyncio.Task] = {}
        self.end = StickyEvent()

    def allocate_stream(self) -> tuple[int, OffsetPublisher]:
        sid = self.next_stream_id
        self.next_stream_id += 1
        pub = OffsetPublisher(-1)
        self.ack_publishers[sid] = pub
        return sid, pub

    async def shutdown(self) -> None:
        self.end.notify()
        for task in self.stream_tasks.values():
            task.cancel()
        if self.stream_tasks:
            await asyncio.gather(*self.stream_tasks.values(), return_exceptions=True)
        self.stream_tasks.clear()


class SpuPublicService(FluvioService[GlobalContext]):
    async def respond(self, ctx: GlobalContext, socket: FluvioSocket) -> None:
        sink = ExclusiveSink(FluvioSink(socket.writer))
        conn = ConnectionContext()
        try:
            while True:
                try:
                    frame = await socket.read_frame()
                except SocketClosed:
                    break
                header, reader = decode_request_header(frame)
                key = header.api_key
                version = header.api_version
                cid = header.correlation_id

                if key == SpuServerApiKey.API_VERSION:
                    ApiVersionsRequest.decode(reader, version)
                    resp = ApiVersionsResponse(api_keys=list(SPU_API_KEYS))
                elif key == SpuServerApiKey.PRODUCE:
                    req = ProduceRequest.decode(reader, version)
                    resp = await handle_produce(ctx, req)
                elif key == SpuServerApiKey.FETCH:
                    req = FetchRequest.decode(reader, version)
                    resp = handle_fetch(ctx, req)
                elif key == SpuServerApiKey.FETCH_OFFSETS:
                    req = FetchOffsetsRequest.decode(reader, version)
                    resp = handle_fetch_offsets(ctx, req)
                elif key == SpuServerApiKey.UPDATE_OFFSETS:
                    req = UpdateOffsetsRequest.decode(reader, version)
                    resp = handle_update_offsets(conn, req)
                elif key == SpuServerApiKey.STREAM_FETCH:
                    req = StreamFetchRequest.decode(reader, version)
                    start_stream_fetch(ctx, conn, req, version, cid, sink)
                    continue  # responses are pushed by the stream task
                else:
                    logger.warning("unknown api key %s", key)
                    break

                await sink.send_response(ResponseMessage(cid, resp), version)
        finally:
            await conn.shutdown()


# ---------------------------------------------------------------------------
# Produce
# ---------------------------------------------------------------------------


async def handle_produce(ctx: GlobalContext, req: ProduceRequest) -> ProduceResponse:
    chain = None
    if req.smartmodules:
        try:
            chain = await asyncio.to_thread(build_chain, req.smartmodules, ctx)
        except (SmartModuleResolutionError, SmartModuleChainInitError, EngineError, SmartModuleFuelError) as e:
            return _produce_error_response(req, _smartmodule_error_code(e), str(e))

    response = ProduceResponse()
    for topic_data in req.topics:
        topic_resp = TopicProduceResponse(name=topic_data.name)
        response.responses.append(topic_resp)
        for pdata in topic_data.partitions:
            presp = PartitionProduceResponse(partition_index=pdata.partition_index)
            topic_resp.partitions.append(presp)
            leader = ctx.leader_for(topic_data.name, pdata.partition_index)
            if leader is None:
                presp.error_code = ErrorCode.NOT_LEADER_FOR_PARTITION
                presp.error_message = (
                    f"{topic_data.name}-{pdata.partition_index} has no leader here"
                )
                continue
            try:
                await ensure_dedup_chain(ctx, leader)
            except SmartModuleResolutionError as e:
                presp.error_code = e.code
                presp.error_message = e.message
                continue
            except Exception as e:  # noqa: BLE001 — chain init boundary
                presp.error_code = ErrorCode.SMARTMODULE_CHAIN_INIT_ERROR
                presp.error_message = str(e)
                continue
            records = pdata.records
            if chain is not None:
                records, err = await _chain_off_loop(
                    chain, _apply_produce_chain, ctx, chain, records
                )
                if err is not None:
                    presp.error_code = ErrorCode.SMARTMODULE_RUNTIME_ERROR
                    presp.error_message = str(err)
                    continue
            try:
                nbytes = sum(b.write_size() for b in records.batches)
                base = await leader.write_record_set(records)
            except FluvioError as e:
                presp.error_code = e.code
                presp.error_message = str(e)
                continue
            presp.base_offset = base
            ctx.metrics.inbound.add(records.total_records(), nbytes)
            if req.isolation == Isolation.READ_COMMITTED:
                await _wait_for_hw(leader, leader.leo(), req.timeout_ms)
    return response



async def _chain_off_loop(chain, fn, *args):
    """Run a per-record chain pass off the event loop.

    Arbitrary Python hooks execute inside these passes; on the loop
    thread a slow or hostile module would stall EVERY connection for
    its metering budget. A worker thread keeps the broker responsive,
    and a per-chain lock serializes passes on shared (cached stateless)
    chains so two streams never run one chain's instances concurrently.
    """
    lock = getattr(chain, "_exec_lock", None)
    if lock is None:
        lock = asyncio.Lock()
        chain._exec_lock = lock
    async with lock:
        return await asyncio.to_thread(fn, *args)


def _apply_produce_chain(ctx: GlobalContext, chain, records: RecordSet):
    """Producer-side transform (parity: produce_handler.rs:215)."""
    return apply_chain(chain, records, ctx.metrics.smartmodule)


async def _wait_for_hw(leader: LeaderReplicaState, target: int, timeout_ms: int) -> None:
    """Block until HW reaches ``target`` (read-committed produce acks)."""
    if leader.hw() >= target:
        return
    listener = leader.hw_publisher.change_listener()
    deadline = asyncio.get_running_loop().time() + timeout_ms / 1000
    while leader.hw() < target:
        remaining = deadline - asyncio.get_running_loop().time()
        if remaining <= 0:
            return
        try:
            await asyncio.wait_for(listener.listen(), timeout=remaining)
        except asyncio.TimeoutError:
            return


def _smartmodule_error_code(e: Exception) -> ErrorCode:
    if isinstance(e, SmartModuleResolutionError):
        return e.code
    if isinstance(e, SmartModuleChainInitError):
        return ErrorCode.SMARTMODULE_CHAIN_INIT_ERROR
    return ErrorCode.SMARTMODULE_ERROR


def _produce_error_response(
    req: ProduceRequest, code: ErrorCode, message: str
) -> ProduceResponse:
    response = ProduceResponse()
    for topic_data in req.topics:
        topic_resp = TopicProduceResponse(name=topic_data.name)
        for pdata in topic_data.partitions:
            topic_resp.partitions.append(
                PartitionProduceResponse(
                    partition_index=pdata.partition_index,
                    error_code=code,
                    error_message=message,
                )
            )
        response.responses.append(topic_resp)
    return response


# ---------------------------------------------------------------------------
# Fetch / FetchOffsets / UpdateOffsets
# ---------------------------------------------------------------------------


def handle_fetch(ctx: GlobalContext, req: FetchRequest) -> FetchResponse:
    resp = FetchResponse(
        topic=req.topic,
        partition=FetchablePartitionResponse(partition_index=req.partition),
    )
    leader = ctx.leader_for(req.topic, req.partition)
    if leader is None:
        resp.partition.error_code = ErrorCode.NOT_LEADER_FOR_PARTITION
        return resp
    info = leader.offsets()
    resp.partition.high_watermark = info.hw
    resp.partition.log_start_offset = info.start_offset
    try:
        rslice = leader.read_records(req.fetch_offset, req.max_bytes, req.isolation)
    except FluvioError as e:
        resp.partition.error_code = e.code
        return resp
    if rslice.file_slice is not None:
        for batch in rslice.decode_batches(parse_records=False):
            resp.partition.records.add(batch)
        ctx.metrics.outbound.add(
            resp.partition.records.total_records(), rslice.file_slice.length
        )
    return resp


def handle_fetch_offsets(ctx: GlobalContext, req: FetchOffsetsRequest) -> FetchOffsetsResponse:
    leader = ctx.leader_for(req.topic, req.partition)
    if leader is None:
        return FetchOffsetsResponse(error_code=ErrorCode.NOT_LEADER_FOR_PARTITION)
    info = leader.offsets()
    return FetchOffsetsResponse(
        start_offset=info.start_offset, hw=info.hw, leo=info.leo
    )


def handle_update_offsets(
    conn: ConnectionContext, req: UpdateOffsetsRequest
) -> UpdateOffsetsResponse:
    resp = UpdateOffsetsResponse()
    for upd in req.offsets:
        pub = conn.ack_publishers.get(upd.session_id)
        if pub is None:
            resp.offsets.append(
                OffsetUpdateStatus(
                    session_id=upd.session_id,
                    error_code=ErrorCode.FETCH_SESSION_NOT_FOUND,
                )
            )
            continue
        pub.update(upd.offset)
        resp.offsets.append(OffsetUpdateStatus(session_id=upd.session_id))
    return resp


# ---------------------------------------------------------------------------
# StreamFetch
# ---------------------------------------------------------------------------


def start_stream_fetch(
    ctx: GlobalContext,
    conn: ConnectionContext,
    req: StreamFetchRequest,
    version: int,
    correlation_id: int,
    sink: ExclusiveSink,
) -> None:
    stream_id, ack_publisher = conn.allocate_stream()
    handler = StreamFetchHandler(
        ctx, conn, req, version, correlation_id, stream_id, sink, ack_publisher
    )
    task = asyncio.ensure_future(handler.run())
    conn.stream_tasks[stream_id] = task

    def _cleanup(_t, sid=stream_id) -> None:
        conn.stream_tasks.pop(sid, None)
        conn.ack_publishers.pop(sid, None)  # dead stream ids stop acking

    task.add_done_callback(_cleanup)


_warmed_chains: "weakref.WeakSet" = weakref.WeakSet()


def _schedule_chain_warmup(chain) -> None:
    """Compile the chain's jit machinery off the hot path.

    First-touch XLA compilation stalls the first consume by tens of
    seconds. Two regimes:

    - **Admission AOT warmup** (``FLUVIO_ADMISSION_WARMUP=1``): the full
      shape-bucket work-list walk (`admission.warmup.warm_executor`) —
      every bucket the chain would compile is paid at attach, the
      warmed buckets register with the admission controller (the
      serve-time gate sheds ``cold-chain`` until then), and stateful
      chains warm safely behind the carry snapshot/restore.
    - **Legacy tiny warm** (default): one 2-record buffer populates the
      fixed per-chain jit costs; stateless chains only (a warmup record
      would race the device carries).
    """
    from fluvio_tpu.admission import warmup as adm_warmup

    tpu = getattr(chain, "tpu_chain", None)
    aot = adm_warmup.warmup_enabled()
    if tpu is None or (tpu.stateful and not aot) or chain in _warmed_chains:
        return
    _warmed_chains.add(chain)
    if aot:
        # the serve gate arms BEFORE the warm thread starts: traffic
        # arriving mid-warmup sheds cold-chain instead of paying the
        # compile inline
        admission_require_warm(chain)

    def _lift_gate() -> None:
        # a failed warmup must not shed the chain forever: lift the
        # gate and serve (cold compiles and all — degraded beats
        # unavailable)
        from fluvio_tpu.spu.smart_chain import (
            _admission_gate,
            admission_chain_sig,
        )

        ctl = _admission_gate()
        if ctl is not None:
            ctl.require_warm(admission_chain_sig(chain), False)

    def _warm() -> None:
        try:
            if aot:
                report = None
                try:
                    report = adm_warmup.warm_executor(tpu)
                finally:
                    # the gate lifts on EVERY outcome: warmed buckets
                    # registered, or (empty report / escaped exception)
                    # explicitly un-gated — never armed-forever
                    if report is not None and report.buckets:
                        admission_note_warm(chain, report.buckets)
                    else:
                        _lift_gate()
                return
            from fluvio_tpu.protocol.record import Record
            from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer

            records = [Record(value=b"[1]"), Record(value=b"[2]")]
            for i, r in enumerate(records):
                r.offset_delta = i
            tpu.process_buffer(RecordBuffer.from_records(records))
        except Exception:  # noqa: BLE001 — warmup is best-effort
            logger.debug("chain warmup failed", exc_info=True)

    try:
        asyncio.get_running_loop().run_in_executor(None, _warm)
    except RuntimeError:  # no loop (sync callers): warm inline
        _warm()



def _process_batches_from(
    chain, batches, max_bytes, metrics, start_offset,
    topic=None, partition=None, flow=None,
):
    return process_batches(
        chain, batches, max_bytes, metrics, start_offset=start_offset,
        topic=topic, partition=partition, flow=flow,
    )


def _advance_slices(
    chain, pending, inflight, nxt_batches, metrics, start_offset, topic,
    partition, nxt_flow,
):
    """Steps 1-3 of the stream loop (`_run_pipelined`), one pass on a
    worker thread: stage slice k+1 (``nxt_batches``; host only), fetch
    slice k (``pending``), then dispatch k+1. ``inflight`` is the
    stream's register of slices with live handles, kept HERE so that a
    cancelled stream still finds what this pass dispatched. Returns
    (slice k fetched, slice k+1 dispatched or None). A staged k+1 is
    not dispatched ahead of a slice k that has to be re-run."""
    staged = None
    if nxt_batches is not None:
        staged = tpu_stage(
            chain, nxt_batches, metrics, start_offset, flow=nxt_flow
        )
    fetched = False
    if pending is not None:
        fetched = tpu_fetch(chain, pending, metrics, topic, partition)
        inflight.remove(pending)  # settled either way
    nxt = None
    if staged is not None and (pending is None or fetched):
        nxt = tpu_dispatch(chain, staged, metrics, topic, partition)
        if nxt is not None:
            inflight.append(nxt)
            if pending is not None and pending.flow is not None:
                pending.flow.interleaved = True
    return fetched, nxt


class StreamFetchHandler:
    """One push stream: select loop over data / acks / end.

    Parity: fluvio-spu/src/services/public/stream_fetch.rs:39 — the handler
    compiles the chain once per stream (`:138`), runs lookback (`:140`),
    then loops: read a bounded slice, push it (zero-copy when no chain,
    engine-processed otherwise, `send_back_records` `:340`), wait for the
    consumer's offset ack, wait for the leader's offsets to advance.
    """

    def __init__(
        self,
        ctx: GlobalContext,
        conn: ConnectionContext,
        req: StreamFetchRequest,
        version: int,
        correlation_id: int,
        stream_id: int,
        sink: ExclusiveSink,
        ack_publisher: OffsetPublisher,
    ):
        self.ctx = ctx
        self.conn = conn
        self.req = req
        self.version = version
        self.correlation_id = correlation_id
        self.stream_id = stream_id
        self.sink = sink
        self.ack_publisher = ack_publisher
        self.metrics = ctx.metrics.smartmodule
        self._ended = False  # terminal error pushed; stop the stream
        # shed-hold visibility (ISSUE-15 satellite): while a slice is
        # held by admission backpressure this stamps the hold start, the
        # held_slices gauge is up, and the release books one
        # admission_hold_seconds observation — a held slice is
        # distinguishable from a hung client on every metrics surface
        self._hold_t0: Optional[float] = None
        # streaming-lag identity: chain@topic/partition for SmartModule
        # streams (matching the admission/SLO key), stream@topic/partition
        # for plain consumes
        self._lag_key = f"stream@{req.topic}/{req.partition}"
        # tenant identity (ISSUE-17 soak plane): the topic-name prefix
        # before the first dot — every served/shed/held count and
        # record-age observation this stream books is tenant-labeled
        self._tenant = tenant_label(req.topic)

    async def run(self) -> None:
        try:
            await self._run()
        except (SocketClosed, ConnectionError, asyncio.CancelledError):
            pass
        except Exception as e:
            logger.exception(
                "stream fetch failed (%s-%s)", self.req.topic, self.req.partition
            )
            # the consumer must learn the stream died (a program fault
            # propagating out of the fused path lands here): without an
            # error frame it waits forever on a stream nobody serves
            try:
                await self._send_error(
                    ErrorCode.SMARTMODULE_RUNTIME_ERROR, hw=-1, log_start=-1,
                    message=f"{type(e).__name__}: {e}",
                )
            except (SocketClosed, ConnectionError, OSError):
                pass
        finally:
            # stream died mid-hold: release through the same path as a
            # re-admit so the gauge drops AND the hold duration is
            # booked (the bare gauge decrement used to lose the
            # admission_hold_seconds observation on disconnect)
            self._release_hold()

    def _note_hold(self) -> None:
        """First shed of a held slice: stamp the hold + raise the gauge
        (idempotent across the retry loop)."""
        if self._hold_t0 is None:
            self._hold_t0 = time.monotonic()
            TELEMETRY.gauge_add("held_slices", 1)
            TELEMETRY.add_tenant_held(self._tenant)

    def _release_hold(self, flow=None) -> None:
        """A held slice was re-admitted: book the hold duration (the
        admission_hold_seconds histogram + the slice's flow record) and
        drop the gauge."""
        if self._hold_t0 is None:
            return
        held_s = time.monotonic() - self._hold_t0
        self._hold_t0 = None
        TELEMETRY.gauge_add("held_slices", -1)
        TELEMETRY.add_slice_phase("hold", held_s)
        if flow is not None:
            flow.hold(held_s)

    async def _run(self) -> None:
        req = self.req
        leader = self.ctx.leader_for(req.topic, req.partition)
        if leader is None:
            await self._send_error(
                ErrorCode.NOT_LEADER_FOR_PARTITION, hw=-1, log_start=-1
            )
            return

        chain = None
        # the flow of the stream's first slice is born with the stream:
        # the first thing that slice waits for is its chain
        first_flow = None
        if req.smartmodules:
            first_flow = TELEMETRY.begin_flow(tenant=self._tenant)
            try:
                # chain build runs @init hooks (user code, metered):
                # keep it off the loop so a looping init stalls only
                # this stream, not every connection
                with timed(first_flow, "chain_acquire"):
                    chain = await asyncio.to_thread(
                        acquire_stream_chain,
                        req.smartmodules,
                        self.ctx,
                        self.version,
                    )
                    await chain_look_back(chain, leader)
            except (
                SmartModuleResolutionError,
                SmartModuleChainInitError,
                EngineError,
                SmartModuleFuelError,
            ) as e:
                info = leader.offsets()
                await self._send_error(
                    _smartmodule_error_code(e),
                    hw=info.hw,
                    log_start=info.start_offset,
                    message=str(e),
                )
                return

        if chain is not None:
            _schedule_chain_warmup(chain)
            self._lag_key = admission_chain_sig(
                chain, req.topic, req.partition
            )
        if TELEMETRY.enabled:
            # register with the lag engine: committed-offset /
            # high-watermark joins for this stream's key from here on
            lag_mod.track_stream(self._lag_key, leader)

        # clamp the starting offset into the valid window (stream_fetch.rs
        # resolves the requested offset against [start, bound])
        info = leader.offsets()
        bound = leader.read_bound(req.isolation)
        current = max(info.start_offset, min(req.fetch_offset, bound))
        if TELEMETRY.enabled and current >= 0:
            # seed the committed cursor at the RESOLVED start: a tail
            # consumer on a deep log must not report the whole log as
            # lag until its first ack (which would false-breach the
            # consumer_lag SLO and shed a caught-up partition)
            lag_mod.note_commit(self._lag_key, current)
        if first_flow is not None:
            first_flow.chain = self._lag_key
            if current >= bound:
                # nothing to serve yet: the open stands on its own, and
                # the first slice's flow is born when the slice arrives
                TELEMETRY.end_flow(first_flow)
                first_flow = None

        end_wait = asyncio.ensure_future(self.conn.end.wait())
        try:
            if getattr(chain, "tpu_chain", None) is not None:
                await self._run_pipelined(
                    leader, chain, end_wait, current, first_flow
                )
                return
            # plain consumes, and chains the chip does not serve (the
            # interpreter and native backends): slice by slice
            flow = first_flow  # the current slice's causal flow record
            while not self.conn.end.is_set() and not self._ended:
                bound = leader.read_bound(req.isolation)
                if current < bound:
                    if chain is not None:
                        # the slice's flow is born at ARRIVAL — before
                        # the admission decision — and survives the
                        # hold-retry loop, so held time is on its record
                        if flow is None:
                            flow = TELEMETRY.begin_flow(
                                self._lag_key, self._tenant
                            )
                        # admission front door: a health/credit shed
                        # HOLDS the slice (offsets untouched — nothing
                        # lost, nothing duplicated); breaker-open
                        # proceeds, the per-record path serves it
                        rej = admission_check(
                            chain, topic=req.topic, partition=req.partition,
                            tenant=self._tenant,
                        )
                        if rej is not None and rej.reason != "breaker-open":
                            if flow is not None:
                                flow.decision = rej.reason
                            self._note_hold()
                            await asyncio.sleep(
                                min(max(rej.retry_after_s, 0.005), 0.25)
                            )
                            continue
                        self._release_hold(flow)
                        if flow is not None:
                            # breaker-open slices serve on the degraded
                            # per-record path — the flow record must say
                            # so, not claim a clean admit
                            flow.decision = (
                                "breaker-open" if rej is not None
                                else "admit"
                            )
                    sent_next, served = await self._send_back_records(
                        leader, chain, current, flow=flow
                    )
                    served_flow, flow = flow, None
                    try:
                        if self._ended:
                            return
                        if sent_next > current:
                            await self._wait_for_ack(
                                sent_next, end_wait, flow=served_flow
                            )
                            current = sent_next
                            continue
                    finally:
                        # the flow closes AFTER its ack wait: `ack_wait`
                        # belongs to the slice it waits for (a slice that
                        # pushed nothing leaves no record, as before)
                        if served is not None:
                            TELEMETRY.end_flow(served_flow, records=served)
                # no data (or empty slice): wait for the log to advance
                listener = leader.offset_publisher(req.isolation).change_listener()
                if leader.read_bound(req.isolation) > current:
                    continue
                listen = asyncio.ensure_future(listener.listen())
                done, _ = await asyncio.wait(
                    [listen, end_wait], return_when=asyncio.FIRST_COMPLETED
                )
                if end_wait in done:
                    listen.cancel()
                    return
        finally:
            end_wait.cancel()

    async def _run_pipelined(
        self, leader, chain, end_wait, current: int, first_flow=None
    ) -> None:
        """The stream loop of every chain the chip serves (stateless,
        fan-out, stateful): one order of a slice's phases.

        1. read, wire_decode, stage of slice k+1: host only;
        2. fetch(k): the blocking half of slice k (`tpu_fetch`), with
           nothing else queued on the chip, so its count-sized slice
           programs run at once, a fan-out overflow retries alone, and
           a stateful slice's carry is settled;
        3. dispatch(k+1): JAX dispatch is async, H2D and device compute
           proceed in the background;
        4. materialize, encode, send, ack_wait of slice k, while the
           chip works on k+1.

        No slice is ever dispatched ahead of a fetch that can roll a
        carry back. A slice that declines in step 4, or a ``max_bytes``
        cut found there (the consume point moved), discards the
        dispatched k+1; so does a stream that ends with one in flight.

        The host halves run OFF the event loop (`_off_loop`), under the
        chain's lock: steps 1-3 as one pass (the log read stays on the
        loop, which owns the replica), step 4's join and encode as
        another; send and ack_wait await on the loop.
        """
        inflight: List[PendingSlice] = []
        try:
            await self._pipelined_loop(
                leader, chain, end_wait, current, first_flow, inflight
            )
        finally:
            # the stream ends (consumer gone, error, end of the
            # connection) with a slice out on the device: nobody will
            # fetch it, so its handles, gauges and carries go back here
            for p in reversed(inflight):
                p.discard(chain.tpu_chain)

    async def _off_loop(self, chain, fn, *args):
        """`_chain_off_loop` for a pass that holds device handles: a
        cancel waits for the pass to settle before it goes on. The
        worker thread cannot be interrupted, and the stream's clean-up
        must not race it for the slice's handles."""
        task = asyncio.ensure_future(_chain_off_loop(chain, fn, *args))
        try:
            return await asyncio.shield(task)
        except asyncio.CancelledError:
            await asyncio.wait([task])
            raise

    async def _pipelined_loop(
        self, leader, chain, end_wait, current, first_flow, inflight
    ) -> None:
        req = self.req
        # the next slice's flow, born at arrival and carried across
        # shed-hold retries until it stages or serves; the stream's
        # first one comes with its `chain_acquire` phase on it
        held_flow = first_flow
        # the slice out on the device (dispatched, not fetched), if any
        pending: Optional[PendingSlice] = None
        while not self.conn.end.is_set() and not self._ended:
            planned = pending.planned_next if pending is not None else current
            nxt: Optional[PendingSlice] = None
            nxt_batches = None
            nxt_flow = None
            read_from = planned
            shed = None
            if planned < leader.read_bound(req.isolation):
                if held_flow is None:
                    held_flow = TELEMETRY.begin_flow(
                        self._lag_key, self._tenant
                    )
                # admission front door for the next slice's read: a shed
                # skips THIS slice's intake (the in-flight one still
                # finishes below) and, when nothing is in flight,
                # sleeps out the backpressure hint — offsets never
                # advance past a shed slice, so the retry re-reads it
                shed = admission_check(
                    chain, topic=req.topic, partition=req.partition,
                    tenant=self._tenant,
                )
                if shed is not None and shed.reason == "breaker-open":
                    # per-record path serves breaker-open; the flow
                    # record keeps the degraded-path label
                    if held_flow is not None:
                        held_flow.decision = "breaker-open"
                    shed = None
                elif shed is not None and held_flow is not None:
                    held_flow.decision = shed.reason
            if shed is None and planned < leader.read_bound(req.isolation):
                self._release_hold(held_flow)
                nxt_flow, held_flow = held_flow, None
                if nxt_flow is not None and nxt_flow.decision != (
                    "breaker-open"
                ):
                    nxt_flow.decision = "admit"
                try:
                    _rslice, nxt_batches = self._read_slice(
                        leader, planned, nxt_flow
                    )
                except FluvioError as e:
                    info = leader.offsets()
                    await self._send_error(
                        e.code, hw=info.hw, log_start=info.start_offset
                    )
                    return

            fetched = False
            if pending is not None or nxt_batches is not None:
                # steps 1-3, one pass off the loop
                fetched, nxt = await self._off_loop(
                    chain, _advance_slices, chain, pending, inflight,
                    nxt_batches, self.metrics, planned, req.topic,
                    req.partition, nxt_flow,
                )

            if pending is not None:
                # step 4, the chip busy with `nxt` meanwhile
                result = None
                if fetched:
                    result = await self._off_loop(
                        chain, tpu_materialize, chain, pending,
                        req.max_bytes, self.metrics, nxt,
                    )
                declined = result is None
                if declined:
                    # rare decline: rerun this slice on the per-record path
                    # (directly — re-entering process_batches would
                    # re-dispatch the failed slice and double-count)
                    result = await self._off_loop(
                        chain, process_batches_per_record,
                        chain, pending.batches, req.max_bytes, self.metrics,
                        pending.flow,
                    )
                served_flow = pending.flow
                served = result.records.total_records()
                try:
                    sent_next = await self._push_processed(
                        leader, result, flow=served_flow
                    )
                    if self._ended:
                        return
                    truncated = sent_next != pending.planned_next
                    pending = None
                    if nxt is not None and (truncated or declined):
                        # `tpu_materialize` discarded it: it read from the
                        # wrong offset, or ran ahead of a slice that is
                        # re-run (its flow record dies with it — never
                        # served)
                        inflight.remove(nxt)
                        nxt = None
                    if truncated:
                        nxt_batches = None
                    await self._wait_for_ack(
                        sent_next, end_wait, flow=served_flow
                    )
                finally:
                    # closed AFTER the ack wait (see `_run`)
                    TELEMETRY.end_flow(served_flow, records=served)
                current = sent_next
                if truncated:
                    continue

            if shed is not None:
                # nothing in flight and this slice was shed: sleep out
                # the backpressure hint before retrying the same offset
                self._note_hold()
                await asyncio.sleep(
                    min(max(shed.retry_after_s, 0.005), 0.25)
                )
                continue
            if nxt is not None:
                pending = nxt
                continue
            if nxt_batches is not None:
                # not dispatched (staging or the dispatch declined it, or
                # the slice before it was re-run): served now, one slice
                # through all its phases, per record where that declines
                result = await self._off_loop(
                    chain, _process_batches_from, chain, nxt_batches,
                    req.max_bytes, self.metrics, read_from,
                    req.topic, req.partition, nxt_flow,
                )
                try:
                    sent_next = await self._push_processed(
                        leader, result, flow=nxt_flow
                    )
                    if self._ended:
                        return
                    sent_next = max(sent_next, read_from)
                    if sent_next > current:
                        await self._wait_for_ack(
                            sent_next, end_wait, flow=nxt_flow
                        )
                        current = sent_next
                finally:
                    TELEMETRY.end_flow(
                        nxt_flow, records=result.records.total_records()
                    )
                continue

            # no pending, no data: wait for the log to advance
            listener = leader.offset_publisher(req.isolation).change_listener()
            if leader.read_bound(req.isolation) > current:
                continue
            listen = asyncio.ensure_future(listener.listen())
            done, _ = await asyncio.wait(
                [listen, end_wait], return_when=asyncio.FIRST_COMPLETED
            )
            if end_wait in done:
                listen.cancel()
                return

    def _read_slice(self, leader, offset: int, flow, decode: bool = True):
        """One slice off the log, under its flow's ``read`` phase: the
        leader's bounded read plus the shallow batch decode (headers
        and raw record slabs; no per-record parse). Returns (read
        slice, batches) — ``batches`` None when the slice is empty or
        ``decode`` is off (the zero-copy path sends the file slice)."""
        req = self.req
        with timed(flow, "read"):
            rslice = leader.read_records(offset, req.max_bytes, req.isolation)
            batches = None
            if (
                decode
                and rslice.file_slice is not None
                and rslice.next_offset is not None
            ):
                batches = rslice.decode_batches(parse_records=False)
        return rslice, batches

    async def _push_processed(
        self, leader, result: BatchProcessResult, flow=None
    ) -> int:
        """Send one processed-slice response; returns the next offset.
        The flow books the response's frame build (the copy of its slab
        into one length-prefixed buffer) as ``frame``, then the socket
        write and drain under the connection's sink lock as ``send``, and
        carries the response's ``stream_id`` / ``next_offset``: the key
        that joins it to the consumer's record of the same response. A
        consumer on this event loop receives the frame inside ``send``
        and ``ack_wait``; its own records name that time."""
        info = leader.offsets()
        partition = FetchablePartitionResponse(
            partition_index=self.req.partition,
            high_watermark=info.hw,
            log_start_offset=info.start_offset,
            next_filter_offset=result.next_offset,
            records=result.records,
        )
        if result.error is not None:
            partition.error_code = ErrorCode.SMARTMODULE_RUNTIME_ERROR
            partition.error_message = str(result.error)
            self._ended = True  # reference ends the stream on transform error
        resp = StreamFetchResponse(
            topic=self.req.topic,
            partition_index=self.req.partition,
            stream_id=self.stream_id,
            partition=partition,
        )
        if flow is not None:
            flow.stream_id = self.stream_id
            flow.next_offset = result.next_offset
        with timed(flow, "frame"):
            frame = ResponseMessage(self.correlation_id, resp).frame_buffer(
                self.version
            )
        with timed(flow, "send"):
            await self.sink.send_frame(frame)
        nbytes = sum(b.write_size() for b in result.records.batches)
        self.ctx.metrics.outbound.add(result.records.total_records(), nbytes)
        if TELEMETRY.enabled and result.records.batches:
            # streaming lag: served-record rate + ONE end-to-end
            # record-age observation per pushed slice (append wall-time
            # from the first output batch's header -> now)
            served = result.records.total_records()
            age_s = lag_mod.serve_age_s(
                result.records.batches[0].header.first_timestamp
            )
            lag_mod.note_serve(self._lag_key, served, age_s)
            TELEMETRY.add_tenant_served(self._tenant, served)
            TELEMETRY.add_tenant_age(self._tenant, age_s)
        return result.next_offset

    async def _wait_for_ack(
        self, target: int, end_wait: asyncio.Future, flow=None
    ) -> None:
        """Backpressure: hold the next push until the consumer acks (the
        ``ack_wait`` phase of the flow whose push is being acked). What
        the wait holds is the consumer's: the rest of the frame's way
        through the socket, its decode and its ack, which a consumer on
        this event loop books as its ``consume_recv`` /
        ``consume_decode`` / ``consume_ack``."""
        listener = self.ack_publisher.change_listener()
        with timed(flow, "ack_wait"):
            while (
                self.ack_publisher.current_value() < target
                and not self.conn.end.is_set()
            ):
                listen = asyncio.ensure_future(listener.listen())
                done, _ = await asyncio.wait(
                    [listen, end_wait], return_when=asyncio.FIRST_COMPLETED
                )
                if end_wait in done:
                    listen.cancel()
                    return
        if TELEMETRY.enabled:
            # the consumer's ack IS the committed offset: the lag
            # engine's join reads hw - committed from here
            acked = self.ack_publisher.current_value()
            if acked >= 0:
                lag_mod.note_commit(self._lag_key, acked)

    async def _send_back_records(
        self, leader, chain, offset: int, flow=None
    ) -> tuple:
        """Push one chunk; returns (next offset, records served): the
        offset unchanged and None when nothing was sent. ``flow`` picks
        up the slice's phases here; the CALLER closes it, after the ack
        wait."""
        req = self.req
        try:
            rslice, batches = self._read_slice(
                leader, offset, flow, decode=chain is not None
            )
        except FluvioError as e:
            info = leader.offsets()
            await self._send_error(e.code, hw=info.hw, log_start=info.start_offset)
            self._ended = True
            return offset, None
        if rslice.file_slice is None or rslice.next_offset is None:
            return offset, None

        info = rslice.start
        if chain is None:
            # zero-copy: stored batches are wire-encoded; sendfile them as
            # the RecordSet body (stream_fetch.rs:443 / sink.rs:123)
            header = ByteWriter()
            header.write_i32(self.correlation_id)
            header.write_string(req.topic)
            header.write_i32(req.partition)
            header.write_i32(self.stream_id)
            header.write_i32(req.partition)  # partition.partition_index
            header.write_u16(int(ErrorCode.NONE))
            header.write_string("")  # error_message
            header.write_i64(info.hw)
            header.write_i64(info.start_offset)
            header.write_i64(rslice.next_offset)
            header.write_i32(rslice.file_slice.length)  # RecordSet byte len
            await self.sink.send_response_with_file_slices(
                header.bytes(), [rslice.file_slice]
            )
            self.ctx.metrics.outbound.add(0, rslice.file_slice.length)
            return rslice.next_offset, None

        # SmartModule path: decode -> chain -> re-batch -> push.
        # Shallow decode (`_read_slice`): the TPU fast path stages raw
        # record slabs into columnar buffers natively; the per-record
        # path parses on demand.
        result: BatchProcessResult = await _chain_off_loop(
            chain, _process_batches_from, chain, batches, req.max_bytes,
            self.metrics, offset, req.topic, req.partition, flow,
        )
        sent_next = await self._push_processed(leader, result, flow=flow)
        return max(sent_next, offset), result.records.total_records()

    async def _send_error(
        self,
        code: ErrorCode,
        hw: int,
        log_start: int,
        message: str = "",
    ) -> None:
        partition = FetchablePartitionResponse(
            partition_index=self.req.partition,
            error_code=code,
            error_message=message,
            high_watermark=hw,
            log_start_offset=log_start,
        )
        resp = StreamFetchResponse(
            topic=self.req.topic,
            partition_index=self.req.partition,
            stream_id=self.stream_id,
            partition=partition,
        )
        await self.sink.send_response(
            ResponseMessage(self.correlation_id, resp), self.version
        )
