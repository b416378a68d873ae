"""Correlation-id multiplexer over one framed socket.

Capability parity: fluvio-socket/src/multiplexing.rs — `MultiplexerSocket`
(`:57`): many concurrent in-flight requests on one TCP connection, each
tagged with a correlation id; a single dispatcher loop per socket routes
response frames to either a oneshot waiter (serial request) or a bounded
queue (server-push stream, `create_stream` `:231` — what powers the
consumer's StreamFetch).

A stream response's way in is timed on the flow tracing's clock (one
`spans.timed` pair a phase, the ``fluvio/<phase>`` annotation with it):
a `ConsumeFlow` per received stream response, with ``consume_recv``
(the payload's arrival after its length prefix), ``consume_decode``
(the response decode) and ``consume_ack`` (the `UpdateOffsetsRequest`
that names the response's stream and next offset). With capture off
`TELEMETRY.begin_consume` returns None and nothing is read or booked.
"""

from __future__ import annotations

import asyncio
from typing import AsyncIterator, Dict, Optional, Union

from fluvio_tpu.protocol.api import (
    ApiRequest,
    RequestMessage,
    decode_response_payload,
)
from fluvio_tpu.telemetry import TELEMETRY
from fluvio_tpu.telemetry.flow import ConsumeFlow
from fluvio_tpu.telemetry.spans import timed
from fluvio_tpu.transport.socket import FluvioSocket, SocketClosed

_STREAM_END = object()


class MultiplexerClosed(ConnectionError):
    """Socket already stale/closed — transient, like SocketClosed."""


class AsyncResponse:
    """Async iterator over server-push responses for one stream request."""

    def __init__(
        self,
        multiplexer: "MultiplexerSocket",
        correlation_id: int,
        msg: RequestMessage,
        queue: asyncio.Queue,
    ):
        self._multiplexer = multiplexer
        self.correlation_id = correlation_id
        self._msg = msg
        self._queue = queue
        # the server's id of this stream, from its first response (the
        # key of the record awaiting its ack)
        self._stream_id: Optional[int] = None

    async def next(self):
        """Next decoded response, or None when the stream/socket ends."""
        item = await self._queue.get()
        if item is _STREAM_END:
            return None
        if isinstance(item, Exception):
            raise item
        payload, rec = item
        with timed(rec, "consume_decode"):
            _, reader = decode_response_payload(payload)
            response = self._msg.request.RESPONSE.decode(
                reader, self._msg.header.api_version
            )
        if rec is not None:
            self._stream_id = self._multiplexer._await_ack(
                rec, response, len(payload)
            )
        return response

    def __aiter__(self) -> AsyncIterator:
        return self

    async def __anext__(self):
        item = await self.next()
        if item is None:
            raise StopAsyncIteration
        return item

    async def close(self) -> None:
        self._multiplexer._drop_stream(self.correlation_id, self._stream_id)


class MultiplexerSocket:
    """Shared multiplexed socket; cheap to clone by reference."""

    def __init__(self, socket: FluvioSocket):
        self._socket = socket
        self._next_correlation = 1
        # cid -> Future (serial) | Queue (stream)
        self._waiters: Dict[int, Union[asyncio.Future, asyncio.Queue]] = {}
        self._send_lock = asyncio.Lock()
        # stream id -> the record of its latest response, until the ack
        # that names it (empty while capture is off)
        self._unacked: Dict[int, ConsumeFlow] = {}
        self._closed = False
        self._closing = False
        self._dispatcher = asyncio.ensure_future(self._dispatch_loop())

    # -- lifecycle ----------------------------------------------------------

    @property
    def is_stale(self) -> bool:
        return self._closed or self._socket.is_stale()

    async def close(self) -> None:
        self._closed = True
        self._closing = True  # deliberate: streams end cleanly, not with error
        self._dispatcher.cancel()
        try:
            await self._dispatcher
        except (asyncio.CancelledError, Exception):
            pass
        await self._socket.close()
        self._fail_all(MultiplexerClosed())

    def _fail_all(self, err: Exception) -> None:
        """Fail serial waiters; end streams (with ``err`` unless closing).

        A deliberate close() delivers a clean end-of-stream; an unexpected
        socket drop delivers the error so continuous consumers can
        distinguish disconnect from end-of-data and reconnect.
        """
        item = _STREAM_END if self._closing else err
        for waiter in list(self._waiters.values()):
            if isinstance(waiter, asyncio.Future):
                if not waiter.done():
                    waiter.set_exception(err)
            else:
                try:
                    waiter.put_nowait(item)
                except asyncio.QueueFull:
                    # slow consumer with a full queue: drop the oldest
                    # buffered response to make room for the terminal item
                    try:
                        waiter.get_nowait()
                        waiter.put_nowait(item)
                    except (asyncio.QueueEmpty, asyncio.QueueFull):
                        pass
        self._waiters.clear()

    def _drop_stream(
        self, correlation_id: int, stream_id: Optional[int] = None
    ) -> None:
        self._waiters.pop(correlation_id, None)
        if stream_id is not None:
            # a response the consumer never acked still closes its record
            TELEMETRY.end_consume(self._unacked.pop(stream_id, None))

    # -- the consumer's records of stream responses -------------------------

    def _await_ack(
        self, rec: ConsumeFlow, response, payload_bytes: int
    ) -> Optional[int]:
        """Fill a decoded stream-fetch response's record and hold it
        until the ack that names its stream and next offset; returns the
        stream id. A response superseded unacked closes without
        ``consume_ack``; a stream response that is no stream fetch (a
        follower's sync) keeps no record. Records come from the batch
        headers, never per record."""
        part = getattr(response, "partition", None)
        if not hasattr(part, "next_filter_offset"):
            return None
        rec.frame_bytes = payload_bytes + 4
        rec.stream_id = response.stream_id
        rec.next_offset = part.next_filter_offset
        rec.records = sum(b.records_len() for b in part.records.batches)
        TELEMETRY.end_consume(self._unacked.pop(rec.stream_id, None))
        self._unacked[rec.stream_id] = rec
        return rec.stream_id

    def _acked_record(self, request: ApiRequest) -> Optional[ConsumeFlow]:
        """The record an `UpdateOffsetsRequest` acknowledges: the one
        held for its (session id = stream id, offset = next offset), or
        None."""
        if not self._unacked:
            return None
        offsets = getattr(request, "offsets", None)
        if not offsets or len(offsets) != 1:
            return None
        rec = self._unacked.get(offsets[0].session_id)
        if rec is None or rec.next_offset != offsets[0].offset:
            return None
        return self._unacked.pop(offsets[0].session_id)

    # -- request paths ------------------------------------------------------

    def _allocate(self, msg: RequestMessage) -> int:
        cid = self._next_correlation
        self._next_correlation += 1
        msg.header.correlation_id = cid
        return cid

    async def send_and_receive(self, request: ApiRequest, version: Optional[int] = None):
        """Serial request: send, await the single matching response."""
        if self.is_stale:
            raise MultiplexerClosed()
        msg = RequestMessage.new_request(request, version)
        cid = self._allocate(msg)
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        self._waiters[cid] = fut
        async with self._send_lock:
            await self._socket.write_frame(msg.encode_payload())
        payload = await fut
        _, reader = decode_response_payload(payload)
        return request.RESPONSE.decode(reader, msg.header.api_version)

    async def create_stream(
        self, request: ApiRequest, version: Optional[int] = None, queue_len: int = 10
    ) -> AsyncResponse:
        """Stream request: send once, then iterate server pushes."""
        if self.is_stale:
            raise MultiplexerClosed()
        msg = RequestMessage.new_request(request, version)
        cid = self._allocate(msg)
        queue: asyncio.Queue = asyncio.Queue(maxsize=queue_len)
        self._waiters[cid] = queue
        async with self._send_lock:
            await self._socket.write_frame(msg.encode_payload())
        return AsyncResponse(self, cid, msg, queue)

    async def send_async(self, request: ApiRequest, version: Optional[int] = None) -> int:
        """Fire-and-forget (e.g. offset acks on a consumer stream). An
        ack books its encode and write as ``consume_ack`` on the record
        of the response it names, and closes that record."""
        rec = self._acked_record(request)
        with timed(rec, "consume_ack"):
            msg = RequestMessage.new_request(request, version)
            cid = self._allocate(msg)
            async with self._send_lock:
                await self._socket.write_frame(msg.encode_payload())
        TELEMETRY.end_consume(rec)
        return cid

    # -- dispatcher ---------------------------------------------------------

    async def _dispatch_loop(self) -> None:
        try:
            while True:
                length = await self._socket.read_frame_length()
                # a frame's record is born with its length prefix; one
                # that turns out not to be a stream response is dropped
                rec = TELEMETRY.begin_consume()
                with timed(rec, "consume_recv"):
                    payload = await self._socket.read_frame_body(length)
                cid, _ = decode_response_payload(payload)
                waiter = self._waiters.get(cid)
                if waiter is None:
                    continue  # response for a dropped/unknown request
                if isinstance(waiter, asyncio.Future):
                    del self._waiters[cid]
                    if not waiter.done():
                        waiter.set_result(payload)
                else:
                    await waiter.put((payload, rec))
        except (SocketClosed, asyncio.CancelledError):
            self._terminal_error = SocketClosed()
        except Exception as e:  # noqa: BLE001 — e.g. corrupt frame DecodeError
            self._terminal_error = e
        finally:
            self._closed = True
            self._socket.set_stale()
            self._fail_all(getattr(self, "_terminal_error", SocketClosed()))
