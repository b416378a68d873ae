"""Multi-device engine mode: the fused chain under `jax.shard_map`.

Capability parity: this is the engine's production multi-chip path (the
"Engine multi-chip sharding" row of the component inventory). The
GSPMD-traced path in `mesh.py` proves sharded equivalence but must
trace with pallas disabled (GSPMD cannot partition `pallas_call`);
`shard_map` places the SAME stage pipeline on each device with the
byte-level pallas kernels active per shard, and the only cross-shard
traffic is what the semantics require: the aggregate carry chain and
window propagation ride explicit `all_gather` prefix fixups
(kernels.assoc_scan_with_prefix) over ICI, everything else is
row-local. Selected by ``SmartEngine(mesh_devices=N)`` /
``SpuConfig.smart_engine.mesh_devices``.
"""

from __future__ import annotations

import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from fluvio_tpu.parallel.mesh import RECORD_AXIS, make_record_mesh
from fluvio_tpu.resilience import faults
from fluvio_tpu.resilience.policy import TRANSIENT, classify, is_program_fault
from fluvio_tpu.telemetry import TELEMETRY
from fluvio_tpu.telemetry.spans import scoped_program, stage_scope, timed
from fluvio_tpu.smartengine.tpu import executor as kernels_executor
from fluvio_tpu.smartengine.tpu import glz, kernels, stripes
from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer, apply_postops_host



def _shard_map(fn, *, mesh, in_specs, out_specs):
    """shard_map with the replication check off: pallas kernels inside
    the shard body require it."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


class ShardedChainExecutor:
    """Row-sharded executor with the single-device executor's surface.

    Supports row-preserving chains (filters / span or byte maps /
    aggregates) AND fan-out (array_map) chains: each shard scatters its
    explode outputs into its own capacity block, the per-shard exact
    totals ride the stacked headers, and a shard whose total exceeds
    its capacity triggers one bigger-capacity retry (mirroring the
    single-device learned-capacity loop). Fan-out composed with an
    aggregate (explode -> count/sum, reference transforms/mod.rs:24-52
    composes all kinds freely) shards too: the handle snapshots the
    pre-dispatch carries, and an overflow retry rolls the cross-shard
    carry chain back to that snapshot before re-dispatching, so the
    abandoned first pass can never double-apply.

    Aggregate carries chain at DISPATCH time through device futures
    (`_pending_carries`), so `process_stream` pipelines sharded
    stateful chains exactly like the single-device executor;
    `discard_dispatch` restores the pre-dispatch futures.
    """

    def __init__(self, executor, n_devices: int, devices=None):
        devs = list(devices if devices is not None else jax.devices())
        if len(devs) < n_devices:
            raise ValueError(
                f"mesh_devices={n_devices} but only {len(devs)} jax devices"
            )
        self.executor = executor
        self.n = n_devices
        self.mesh = make_record_mesh(n_devices, devices=devs)
        self._jit_cache: Dict = {}
        # device-future carries of the most recent dispatch (stream
        # pipelining); None = the host mirror is authoritative
        self._pending_carries = None
        self.fanout_retries = 0  # observability: capacity-retry count

    # -- traced step ---------------------------------------------------------

    def _local_step_striped(
        self, uploads: Dict, count, base_ts, carries, *, cfg: tuple
    ):
        """Striped wide-record step: each shard derives its own stripe
        plan from its local lengths — stripes never split across shard
        boundaries because the ragged staging already cuts the flat at
        shard ROW boundaries (whole records per shard). The segment axis
        is the record axis, so the survivor mask, aggregate columns, and
        cross-shard carry collectives are the narrow sharded path's,
        unchanged. Span chains (striped JsonGet map) additionally ship
        per-shard compacted view descriptors; ``kmax`` bounds their
        cross-stripe carry's outer scan."""
        (_width, kwidth, has_keys, has_offsets, ts_mode,
         _enc, _cap, srows, kmax) = cfg
        ex = self.executor
        s, v = ex._stripe_s, ex._stripe_v
        with jax.named_scope("repad"):
            lengths = uploads["lengths"].astype(jnp.int32)
            n_local = lengths.shape[0]
            g0 = lax.axis_index(RECORD_AXIS) * n_local
            live = (g0 + jnp.arange(n_local, dtype=jnp.int32)) < count
            plan = stripes.plan_device(lengths, live, srows, s, v)
            sv = stripes.striped_repad_words(
                uploads["flat_words"], lengths, plan, s
            )
            keys, key_lengths, offset_deltas, timestamp_deltas = (
                kernels_executor.derived_meta_columns(
                    n_local, kwidth,
                    has_keys, uploads.get("keys"), uploads.get("key_lengths"),
                    has_offsets, uploads.get("offset_deltas"),
                    ts_mode, uploads.get("timestamp_deltas"),
                    idx_base=g0,
                )
            )
            arrays = {
                "keys": keys,
                "key_lengths": key_lengths,
                "offset_deltas": offset_deltas,
                "timestamp_deltas": timestamp_deltas,
            }
            seg_state = stripes.seg_state_of(plan, sv, lengths, arrays, s)
        ctx = {
            "sv": sv, "plan": plan, "seg_state": seg_state, "n": n_local,
            "kmax": kmax,
        }
        valid, seg_state, carries, _fan, vspan = ex._striped.run(
            ctx, live, carries, base_ts,
            {"fanout_cap": None, "axis_name": RECORD_AXIS, "g0": g0},
        )
        with jax.named_scope("compact"):
            return self._striped_outputs(
                valid, seg_state, carries, vspan, lengths
            )

    def _striped_outputs(self, valid, seg_state, carries, vspan, lengths):
        """`_local_step_striped`'s tail under the ``compact`` device
        scope (the single-device `_chain_outputs` vocabulary)."""
        ex = self.executor
        cnt = jnp.sum(valid.astype(jnp.int32))

        def header(max_v):
            return jnp.stack(
                [
                    cnt.astype(jnp.int64),
                    max_v.astype(jnp.int64),
                    jnp.int64(0),
                    jnp.int64(0),
                    jnp.int64(0),
                ]
            )[None, :]

        packed: Dict = {"mask": kernels.pack_mask(valid)}
        if ex._int_output:
            windowed = bool(ex.stages[-1].window_ms)
            cols = [seg_state["agg_out_int"]]
            if windowed:
                cols.append(seg_state["agg_win_int"])
            _, compacted = kernels.compact_rows(valid, *cols)
            packed["agg_int"] = compacted[0]
            if windowed:
                packed["agg_win"] = compacted[1]
            return header(jnp.int32(0)), packed, carries
        if vspan is not None:
            # span-view chain: survivors are sub-record views — ship the
            # compacted per-shard descriptors (single-device packing,
            # per shard block)
            st, ln = vspan
            _, compacted = kernels.compact_rows(
                valid, st.astype(jnp.int32), ln.astype(jnp.int32)
            )
            packed["span_start"] = compacted[0]
            packed["span_len"] = compacted[1]
            return header(jnp.max(compacted[1])), packed, carries
        return header(jnp.max(jnp.where(valid, lengths, 0))), packed, carries

    def _local_step_ragged(
        self, uploads: Dict, count, base_ts, carries, *, cfg: tuple
    ):
        """Rebuild this shard's padded arrays from its ragged upload, then
        run the stage pipeline (same device-side re-pad as the single
        device `_chain_fn_ragged`: the host link carries sum(lengths)
        bytes per shard, not rows x width)."""
        (width, kwidth, has_keys, has_offsets, ts_mode,
         enc, fanout_cap) = cfg
        with jax.named_scope("repad"):
            values, lengths = kernels_executor.ragged_repad_words(
                uploads["flat_words"], uploads["lengths"], width
            )
            n_local = lengths.shape[0]
            g0 = lax.axis_index(RECORD_AXIS) * n_local
            keys, key_lengths, offset_deltas, timestamp_deltas = (
                kernels_executor.derived_meta_columns(
                    n_local, kwidth,
                    has_keys, uploads.get("keys"), uploads.get("key_lengths"),
                    has_offsets, uploads.get("offset_deltas"),
                    ts_mode, uploads.get("timestamp_deltas"),
                    idx_base=g0,
                )
            )
        arrays = {
            "values": values,
            "lengths": lengths,
            "keys": keys,
            "key_lengths": key_lengths,
            "offset_deltas": offset_deltas,
            "timestamp_deltas": timestamp_deltas,
        }
        return self._local_step(
            arrays, count, base_ts, carries, fanout_cap, enc=enc
        )

    def _local_step(self, arrays: Dict, count, base_ts, carries, fanout_cap=None,
                    enc: str = "off"):
        ex = self.executor
        ax = RECORD_AXIS
        n_local = arrays["values"].shape[0]
        g0 = lax.axis_index(ax) * n_local
        gidx = g0 + jnp.arange(n_local, dtype=jnp.int32)
        state = dict(arrays)
        state["valid"] = gidx < count
        state["view_start"] = jnp.zeros((n_local,), dtype=jnp.int32)
        state["src_row"] = gidx
        # fanout_cap is PER SHARD: each shard scatters into its own
        # capacity block; src_row stays global so the host gather works
        ctx = {"fanout_cap": fanout_cap, "axis_name": ax, "g0": g0}
        for i, stage in enumerate(ex.stages):
            ctx["stage_index"] = i
            with jax.named_scope(stage_scope(i, stage.kind)):
                state, carries = stage.apply(state, carries, base_ts, ctx)
        with jax.named_scope("compact"):
            return self._local_outputs(arrays, state, carries, enc)

    def _local_outputs(self, arrays: Dict, state: Dict, carries, enc: str):
        """`_local_step`'s tail under the ``compact`` device scope."""
        ex = self.executor
        valid = state["valid"]
        cnt = jnp.sum(valid.astype(jnp.int32))
        fan_err = state.get("fan_err", jnp.asarray(False))
        fan_total = state.get("fan_total", jnp.int32(0))

        def header(max_v, max_k):
            return jnp.stack(
                [
                    cnt.astype(jnp.int64),
                    max_v.astype(jnp.int64),
                    max_k.astype(jnp.int64),
                    fan_err.astype(jnp.int64),
                    fan_total.astype(jnp.int64),
                ]
            )[None, :]

        packed: Dict = {}
        if not ex._fanout:
            packed["mask"] = kernels.pack_mask(valid)
        if ex._viewable:
            cols = [state["view_start"], state["lengths"]]
            if ex._fanout:
                cols.append(state["src_row"])
            _, compacted = kernels.compact_rows(valid, *cols)
            packed["span_start"] = compacted[0]
            packed["span_len"] = compacted[1]
            if ex._fanout:
                packed["src_row"] = compacted[2]
            if enc != "off":
                # per-shard down-link encode under shard_map (the same
                # interleaved descriptor stream the single-device chain
                # emits, one independent token set per shard)
                ex._down_encode(
                    packed,
                    ex._desc_stream(
                        compacted[0], compacted[1],
                        arrays["values"].shape[1],
                    ),
                    enc,
                )
                packed["down_meta"] = packed["down_meta"][None, :]
            return header(jnp.max(compacted[1]), jnp.int32(0)), packed, carries
        if ex._int_output:
            windowed = bool(ex.stages[-1].window_ms)
            cols = [state["agg_out_int"]]
            if windowed:
                cols.append(state["agg_win_int"])
            if ex._fanout:  # survivor recovery for explode -> aggregate
                cols.append(state["src_row"])
            _, compacted = kernels.compact_rows(valid, *cols)
            packed["agg_int"] = compacted[0]
            if windowed:
                packed["agg_win"] = compacted[1]
            if ex._fanout:
                packed["src_row"] = compacted[-1]
            return header(jnp.int32(0), jnp.int32(0)), packed, carries
        cols = [
            state["values"],
            state["lengths"],
            state["keys"],
            state["key_lengths"],
        ]
        if ex._fanout:
            cols.append(state["src_row"])
        _, compacted = kernels.compact_rows(valid, *cols)
        packed["values"] = compacted[0]
        packed["lengths"] = compacted[1]
        packed["keys"] = compacted[2]
        packed["key_lengths"] = compacted[3]
        if ex._fanout:
            packed["src_row"] = compacted[4]
        return (
            header(jnp.max(compacted[1]), jnp.max(compacted[3])),
            packed,
            carries,
        )

    def _jitted(self, uploads: Dict, cfg: tuple):
        striped = len(cfg) == 9  # (..., enc, fanout_cap, srows, kmax)
        key = (
            tuple(sorted((k, v.shape, str(v.dtype)) for k, v in uploads.items())),
            cfg,
        )
        fn = self._jit_cache.get(key)
        if fn is None:
            row = P(RECORD_AXIS)
            mat = P(RECORD_AXIS, None)
            rep = P()
            in_specs = (
                {k: (mat if v.ndim == 2 else row) for k, v in uploads.items()},
                rep,
                rep,
                jax.tree_util.tree_map(lambda _: rep, self._carries()),
            )
            out_specs = (
                row,  # per-shard (1, 5) headers stack to (n, 5)
                self._packed_specs(striped, cfg[5]),
                jax.tree_util.tree_map(lambda _: rep, self._carries()),
            )

            local_step = (
                self._local_step_striped if striped else self._local_step_ragged
            )

            def step(uploads, count, base_ts, carries):
                return local_step(uploads, count, base_ts, carries, cfg=cfg)

            from fluvio_tpu.telemetry import instrument_jit

            # compile observability: a fresh (shapes, cfg) key means a
            # fresh shard_map program — the wrapper records the compile
            # with the chain signature + mesh width + static cfg tuple
            sig = (
                f"{getattr(self.executor, '_chain_sig', '?')} "
                f"n={self.n} cfg={cfg}"
            )
            fn = instrument_jit(
                jax.jit(
                    _shard_map(
                        scoped_program(step),
                        mesh=self.mesh,
                        in_specs=in_specs,
                        out_specs=out_specs,
                    )
                ),
                "sharded",
                describe=lambda *a, _sig=sig, **k: _sig,
            )
            self._jit_cache[key] = fn
        return fn

    def _packed_specs(self, striped: bool = False, enc: str = "off"):
        row = P(RECORD_AXIS)
        mat = P(RECORD_AXIS, None)
        ex = self.executor
        if striped:
            # striped chains ship the segment mask, plus the compacted
            # int columns (aggregate tails) or view descriptors (span
            # chains)
            out = {"mask": row}
            if ex._int_output:
                out["agg_int"] = row
                if bool(ex.stages[-1].window_ms):
                    out["agg_win"] = row
            elif ex._striped_has_span():
                out["span_start"] = row
                out["span_len"] = row
            return out
        if ex._viewable:
            out = {"span_start": row, "span_len": row}
            if ex._fanout:
                out["src_row"] = row
            else:
                out["mask"] = row
            if enc != "off":
                out.update(
                    down_ll=row, down_ml=row, down_src=row,
                    down_lits=row, down_meta=mat,
                )
            return out
        if ex._int_output:
            out = {"agg_int": row}
            out["src_row" if ex._fanout else "mask"] = row
            if bool(ex.stages[-1].window_ms):
                out["agg_win"] = row
            return out
        out = {
            "values": mat,
            "lengths": row,
            "keys": mat,
            "key_lengths": row,
        }
        if ex._fanout:
            out["src_row"] = row
        else:
            out["mask"] = row
        return out

    # -- execution -----------------------------------------------------------

    def _carries(self):
        if self._pending_carries is not None:
            return self._pending_carries
        return tuple(
            (jnp.int64(acc), jnp.int64(win), jnp.asarray(has))
            for acc, win, has in self.executor.carries
        )

    def _row_blocks(self, rows: int) -> tuple:
        """(total padded rows, rows per shard): shards must hold a
        multiple of 8 rows so each shard's survivor bitmask packs to
        whole bytes and the concatenated per-shard masks line up with
        global row numbering bit-for-bit."""
        step = self.n * 8
        need = max(step, ((rows + step - 1) // step) * step)
        return need, need // self.n

    def _shard_segments(self, buf: RecordBuffer) -> np.ndarray:
        """Per-shard flat segments for the ragged staging: the aligned
        flat cut at shard row boundaries, each segment padded to one
        bucketed length (equal shapes keep one compiled program).
        Shards over the LIVE rows (bucketed), not the buffer's pow2 row
        padding — trailing all-padding shards would otherwise still
        ship seg_len bytes each. Returns segs uint8[n, seg_len]."""
        ex = self.executor
        _need, shard_rows = self._row_blocks(min(buf.count, buf.rows))
        flat, starts = buf.ragged_values()
        lengths4 = (buf.lengths.astype(np.int64) + 3) & ~3
        total = int(lengths4.sum())
        # segment bounds at shard row boundaries (rows past buf.rows are
        # zero-length padding and contribute no bytes)
        cuts = [0]
        for s in range(1, self.n):
            r = s * shard_rows
            cuts.append(int(starts[r]) if r < len(starts) else total)
        cuts.append(total)
        seg_sizes = np.diff(cuts)
        seg_len = ex._bucket_bytes(max(int(seg_sizes.max()), 4))
        segs = np.zeros((self.n, seg_len), dtype=np.uint8)
        for s in range(self.n):
            segs[s, : seg_sizes[s]] = flat[cuts[s] : cuts[s + 1]]
        return segs

    def _stage_ragged(self, buf: RecordBuffer) -> tuple:
        """Ragged H2D staging (the single-device link diet, per shard).

        The aligned flat is cut at shard row boundaries; every shard's
        segment pads to one bucketed segment length (equal shapes keep
        one compiled program) and ships as i32 words. Derivable columns
        never cross the link: arange offsets and zero timestamps are
        synthesized on device, timestamps narrow to i32 when they fit,
        lengths ride the narrowest of u8/u16 the record width allows.
        Returns (uploads dict, static cfg, H2D byte count).
        """
        need, _shard_rows = self._row_blocks(min(buf.count, buf.rows))
        segs = self._shard_segments(buf)
        flat_words = segs.reshape(-1).view(np.int32)

        def pad_rows(a, fill=0):
            pad = need - a.shape[0]
            if pad == 0:
                return a
            if pad < 0:  # buffer's pow2 row padding exceeds the live need
                return a[:need]
            widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
            return np.pad(a, widths, constant_values=fill)

        lengths_np, has_keys, has_offsets, ts_mode, ts_np = (
            kernels_executor.stage_link_columns(buf)
        )
        uploads = {"flat_words": flat_words, "lengths": pad_rows(lengths_np)}
        if has_keys:
            uploads["keys"] = pad_rows(buf.keys)
            uploads["key_lengths"] = pad_rows(buf.key_lengths, fill=-1)
        if has_offsets:
            uploads["offset_deltas"] = pad_rows(buf.offset_deltas)
        if ts_np is not None:
            uploads["timestamp_deltas"] = pad_rows(ts_np)
        cfg = (buf.width, buf.keys.shape[1], has_keys, has_offsets, ts_mode)
        return uploads, cfg, sum(v.nbytes for v in uploads.values())

    def _shard_fanout_cap(self, buf: RecordBuffer, cap_total=None) -> int:
        """Per-shard explode capacity: the learned global capacity split
        across shards with 1.5x headroom for imbalance (a shard whose
        exact total still exceeds it triggers the retry)."""
        ex = self.executor
        if cap_total is None:
            cap_total = ex._fanout_cap(buf)
        return ex._bucket_bytes(max(cap_total * 3 // (2 * self.n), 8), 8)

    def _stripe_rows_shard(self, buf: RecordBuffer) -> int:
        """Static per-shard stripe-row count: every shard compiles to the
        worst shard's (bucketed) stripe total so shapes stay uniform
        under shard_map."""
        ex = self.executor
        _need, shard_rows = self._row_blocks(min(buf.count, buf.rows))
        worst = 8
        for s in range(self.n):
            lo = s * shard_rows
            hi = min((s + 1) * shard_rows, buf.count)
            if hi > lo:
                worst = max(
                    worst,
                    int(
                        stripes.stripe_counts(
                            buf.lengths[lo:hi], ex._stripe_s, ex._stripe_v
                        ).sum()
                    ),
                )
        return ex._bucket_bytes(worst, floor=8)

    def dispatch_buffer(self, buf: RecordBuffer, cap_shard=None, reuse_span=None):
        # The dispatch-side transfer-guard scope lives HERE, not at the
        # call sites: every entry point — the executor delegation, the
        # fanout-cap re-dispatch inside finish_buffer, the transient
        # retry in _finish_sharded_inner (both of which otherwise run
        # inside the fetch ALLOW scope), and direct process_buffer
        # drivers — is dispatch-hot and must not be allowlisted.
        with kernels_executor.transfer_guard_dispatch():
            return self._dispatch_buffer_inner(buf, cap_shard, reuse_span)

    def _dispatch_buffer_inner(self, buf: RecordBuffer, cap_shard, reuse_span):
        from fluvio_tpu.smartengine.tpu.executor import TpuSpill

        ex = self.executor
        # a fan-out retry passes the batch's ORIGINAL span back in so the
        # retry's stage/h2d/dispatch/device time accumulates onto it
        # instead of a second span that would be discarded
        span = (
            reuse_span
            if reuse_span is not None
            else TELEMETRY.begin_batch(chain=ex._chain_sig)
        )
        t_ph = time.perf_counter() if span is not None else 0.0
        faults.maybe_fire("stage")
        striped = ex._needs_stripes(buf)
        uploads, cfg, nbytes = self._stage_ragged(buf)
        if span is not None:
            now = time.perf_counter()
            span.add("stage", now - t_ph)
            t_ph = now
        if ex._fanout and cap_shard is None:
            cap_shard = self._shard_fanout_cap(buf)
        # sharded down-link encode: the shared arming rule, further
        # restricted to narrow viewable/fan-out chains (sharded striped
        # keeps its raw descriptor ship — the per-shard token-bucket
        # axis would square the worst-shard compile matrix; sharded
        # byte-mode keeps the padded ship, so packing stays off here too)
        enc_sh = ex._down_axes(striped)[0] if ex._viewable else "off"
        cfg = cfg + (enc_sh, cap_shard)
        if striped:
            if ex._striped_chain() is None or ex._fanout:
                # wide batch outside the sharded stripeable subset
                # (fan-out explodes stay single-device or interpret)
                TELEMETRY.add_stripe_fallback()
                raise TpuSpill(
                    f"record width {buf.width} exceeds the narrow layout "
                    "and the chain cannot stripe under shard_map",
                    reason="record-too-wide-unstripeable",
                )
            cfg = cfg + (self._stripe_rows_shard(buf), ex._stripe_kmax(buf))
            if span is not None:
                span.path = "striped"
        faults.maybe_fire("h2d")
        sharded = {
            k: jax.device_put(
                v,
                NamedSharding(
                    self.mesh, P(RECORD_AXIS, None) if v.ndim == 2 else P(RECORD_AXIS)
                ),
            )
            for k, v in uploads.items()
        }
        if span is not None:
            now = time.perf_counter()
            span.add("h2d", now - t_ph)
            t_ph = now
        fn = self._jitted(sharded, cfg)
        faults.maybe_fire("dispatch")
        prev_carries = self._pending_carries
        try:
            header, packed, new_carries = fn(
                sharded,
                jnp.int32(buf.count),
                jnp.int64(buf.base_timestamp),
                self._carries(),
            )
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:
            if is_program_fault(e):
                # lowering/compile errors are the program's, not the
                # device's: no quieter rung answers them
                raise
            if enc_sh != "off" and classify(e) != TRANSIENT:
                # sync half of the sharded ENCODE ladder (runtime
                # failures): latch encode off and re-dispatch the same
                # batch (the encoder is output-side)
                ex._enc_demote(e, where="sharded dispatch")
                return self._dispatch_buffer_inner(buf, cap_shard, span)
            # anything else (a transient hiccup included) is the
            # executor's bounded dispatch retry's to answer
            raise
        if span is not None:
            span.add("dispatch", time.perf_counter() - t_ph)
            span.mark_dispatched()
        # byte accounting only after the dispatch commits: a retried
        # attempt that failed mid-staging must not double-count the link
        ex.h2d_bytes_total += nbytes
        if ex.agg_configs:
            # carries chain through device futures at dispatch time so
            # streams pipeline; the host mirror commits at finish
            self._pending_carries = new_carries
        return (
            prev_carries, new_carries, header, packed, cap_shard, span,
            enc_sh if enc_sh != "off" else None,
        )

    def discard_dispatch(self, handle) -> None:
        """Drop a speculative dispatch, restoring pre-dispatch carries."""
        if self.executor.agg_configs:
            self._pending_carries = handle[0]

    def _shard_slices(self, arr, counts, vw: int = 0):
        """Per-shard row slices bounded by that shard's survivor count
        (bucketed), sliced device-side so the D2H link never carries the
        padded remainder of each shard's block."""
        from jax import lax as jlax

        ex = self.executor
        shard_rows = arr.shape[0] // self.n
        out = []
        for s in range(self.n):
            rows = min(ex._bucket_bytes(max(int(counts[s]), 1), 8), shard_rows)
            if arr.ndim == 2:
                w = min(vw or arr.shape[1], arr.shape[1])
                out.append(
                    jlax.slice(arr, (s * shard_rows, 0), (s * shard_rows + rows, w))
                )
            else:
                out.append(
                    jlax.slice(arr, (s * shard_rows,), (s * shard_rows + rows,))
                )
        return out

    @staticmethod
    def _concat_counts(parts, counts):
        return np.concatenate(
            [np.asarray(p)[: int(c)] for p, c in zip(parts, counts)]
        )

    def _try_down_fetch(
        self, buf, packed, down_meta, counts, _fetch_all, width: int,
    ):
        """Sharded fetch half of the result-encode ladder: download each
        shard's token slices (one concurrent `_fetch_all`, survivor
        recovery riding along), inflate per shard, split the descriptor
        columns. Returns (src, st, ln) or None when the tokens lose the
        whole-batch ratio race (counted as `glz-enc-ratio`) or a decode
        fails (one rung down via `_enc_demote`; caller re-fetches the
        raw columns, which are in ``packed`` regardless)."""
        ex = self.executor
        n = self.n
        G = packed["down_ll"].shape[0] // n
        L = packed["down_lits"].shape[0] // n
        n_desc = packed["span_start"].shape[0] // n  # descriptor cap/shard
        desc_width = width
        f_st, f_ln = ex._desc_fields(desc_width)
        buckets = []
        token_total = 0
        raw_total = 0
        for s in range(n):
            ns, nl = int(down_meta[s, 0]), int(down_meta[s, 1])
            bs = min(ex._bucket_bytes(max(ns, 8), floor=256), G)
            bl = min(ex._bucket_bytes(max(nl, 8), floor=256), L)
            buckets.append((bs, bl))
            token_total += bs * 6 + bl
            rows_s = min(ex._bucket_bytes(max(int(counts[s]), 1), 8), n_desc)
            raw_total += rows_s * (f_st + f_ln)
        if token_total >= raw_total:
            TELEMETRY.add_decline(glz.DECLINE_ENC_RATIO)
            ex.tag_decline(glz.DECLINE_ENC_RATIO)
            return None
        from jax import lax as jlax

        slices = []
        for s in range(n):
            bs, bl = buckets[s]
            for name, base_len, b in (
                ("down_ll", G, bs), ("down_ml", G, bs),
                ("down_src", G, bs), ("down_lits", L, bl),
            ):
                slices.append(
                    jlax.slice(
                        packed[name], (s * base_len,), (s * base_len + b,)
                    )
                )
        src, (tok,) = _fetch_all(slices)
        st_parts, ln_parts = [], []
        pos = 0
        for s in range(n):
            ll_h, ml_h, sc_h, li_h = tok[pos : pos + 4]
            pos += 4
            ns, nl, dep = (int(x) for x in down_meta[s])
            try:
                stream = glz.decode_result_host(
                    np.asarray(ll_h), np.asarray(ml_h), np.asarray(sc_h),
                    np.asarray(li_h), ns, nl, L, dep,
                )
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                ex._enc_demote(e, where="sharded fetch")
                return None
            st_s, ln_s = ex._desc_split(stream, int(counts[s]), desc_width)
            st_parts.append(st_s)
            ln_parts.append(ln_s)
        st = np.concatenate(st_parts).astype(np.int64)
        ln = np.concatenate(ln_parts).astype(np.int32)
        return src, st, ln

    def finish_buffer(self, buf: RecordBuffer, handle) -> RecordBuffer:
        from fluvio_tpu.smartengine.tpu.executor import TpuSpill

        (_prev, new_carries, header, packed, cap_shard, span, _enc) = handle
        t_f0 = time.perf_counter() if span is not None else 0.0
        d2h0 = span.phase("d2h") if span is not None else 0.0
        ex = self.executor
        # device-side failures surface at the first blocking sync
        faults.maybe_fire("device")
        down_meta = None
        # the stacked-header sync is where this thread blocks on the
        # mesh: the span's `wait` (the single-device `_fetch_inner` pair)
        with timed(span, "wait"):
            if "down_meta" in packed:
                hdr_got = jax.device_get([header, packed["down_meta"]])
                hdrs = np.asarray(hdr_got[0])  # (n_shards, 5)
                down_meta = np.asarray(hdr_got[1])  # (n_shards, 3)
            else:
                hdrs = np.asarray(jax.device_get(header))  # (n_shards, 5)
        if span is not None:
            span.mark_device_ready()
        counts = hdrs[:, 0].astype(np.int64)
        total = int(counts.sum())
        n_rows = buf.rows
        width = buf.width
        if ex._fanout:
            if hdrs[:, 3].any():
                # carries the abandoned dispatch advanced roll back to
                # the handle's snapshot before the interpreter re-runs
                self._pending_carries = _prev
                raise TpuSpill("array_map transform error: interpreter decides")
            totals = hdrs[:, 4].astype(np.int64)
            if int(totals.max()) > cap_shard:
                # one bigger-capacity retry at the exact (bucketed)
                # per-shard maximum. An aggregate downstream of the
                # explode advanced the cross-shard carry chain on the
                # abandoned dispatch: restore the handle's pre-dispatch
                # snapshot first so the retry chains from clean state
                # and can never double-apply. Learn from the PER-SHARD
                # peak (scaled to a global total), not the global sum:
                # a persistently skewed stream would otherwise
                # overflow-and-retry every batch
                self._pending_carries = _prev
                ex._learn_cap(buf, int(totals.max()) * self.n)
                self.fanout_retries += 1
                retry_cap = ex._bucket_bytes(int(totals.max()), 8)
                handle = self.dispatch_buffer(
                    buf, cap_shard=retry_cap, reuse_span=span
                )
                (_prev, new_carries, header, packed, cap_shard, _,
                 _enc) = handle
                with timed(span, "wait"):
                    down_meta = (
                        np.asarray(jax.device_get(packed["down_meta"]))
                        if "down_meta" in packed
                        else None
                    )
                    hdrs = np.asarray(jax.device_get(header))
                if span is not None:
                    span.mark_device_ready()
                if int(hdrs[:, 4].max()) > cap_shard:  # pragma: no cover
                    self._pending_carries = _prev
                    raise TpuSpill(
                        f"fanout overflow after retry: {int(hdrs[:, 4].max())}",
                        reason="fanout-overflow",
                    )
                counts = hdrs[:, 0].astype(np.int64)
                total = int(counts.sum())
        cap_rows = self.n * cap_shard if ex._fanout else n_rows
        rows_out = min(ex._bucket_bytes(max(total, 1), 8), max(cap_rows, 8))

        # one async fetch for every column: all shard slices start their
        # D2H copies concurrently (same pattern as the single-device
        # _fetch) instead of one blocking round-trip per column.
        # Survivor recovery: row-preserving chains ship the 1-bit mask;
        # fan-out chains ship the explicit per-shard src_row slices
        # (global input row indices, so the host gather is unchanged).
        def _fetch_all(*column_groups):
            if ex._fanout:
                src_slices = self._shard_slices(
                    ex._narrow_static(packed["src_row"], max(n_rows, 1)),
                    counts,
                )
                cols = list(src_slices)
                n_lead = len(cols)
            else:
                cols = [packed["mask"]]
                n_lead = 1
            for group in column_groups:
                cols.extend(group)
            # the executor's single download point: byte accounting rides
            # along for sharded batches too
            host = ex._download(cols, span)
            if ex._fanout:
                src_h = self._concat_counts(host[:n_lead], counts).astype(
                    np.int64
                )
            else:
                src_h = np.flatnonzero(
                    np.unpackbits(np.asarray(host[0]), bitorder="little")[
                        :n_rows
                    ]
                )
            groups, pos = [], n_lead
            for group in column_groups:
                groups.append(host[pos : pos + len(group)])
                pos += len(group)
            return src_h, groups

        if ex._viewable:
            used_tokens = None
            desc_cols = None
            if down_meta is not None:
                desc_cols = self._try_down_fetch(
                    buf, packed, down_meta, counts, _fetch_all, width
                )
                if desc_cols is not None:
                    used_tokens = "xla"
            if ex._needs_stripes(buf) and "span_start" not in packed:
                # striped survivors are whole records: the segment mask
                # is the entire download; spans derive host-side (span
                # chains DO carry descriptors and take the branch below)
                src, _ = _fetch_all()
                st = np.zeros(total, dtype=np.int64)
                ln = buf.lengths[src[:total]].astype(np.int32)
            elif desc_cols is not None:
                src, st, ln = desc_cols
            else:
                # span descriptors are width-bounded: ship them at the
                # same narrow dtype the single-device fetch uses
                src, (st_parts, ln_parts) = _fetch_all(
                    self._shard_slices(
                        ex._narrow_static(packed["span_start"], width), counts
                    ),
                    self._shard_slices(
                        ex._narrow_static(packed["span_len"], width + 1),
                        counts,
                    ),
                )
                st = self._concat_counts(st_parts, counts).astype(np.int64)
                ln = self._concat_counts(ln_parts, counts).astype(np.int32)
            ex._count_down_variant(used_tokens)
            vw = int(max(int(hdrs[:, 1].max()), 1))
            vw = min(ex._pad_slice(vw), width)
            out_values = np.zeros((rows_out, vw), dtype=np.uint8)
            if total:
                keep = np.arange(vw, dtype=np.int32)[None, :] < ln[:, None]
                if buf.values is None:
                    # flat-backed buffer (the broker path): slice views
                    # straight out of the aligned flat — never build the
                    # rows x width dense matrix the ragged staging avoided
                    flat, starts = buf.ragged_values()
                    if len(flat):
                        base = starts.astype(np.int64)[src[:total]] + st
                        cols = (
                            base[:, None]
                            + np.arange(vw, dtype=np.int64)[None, :]
                        )
                        gathered = flat[np.clip(cols, 0, len(flat) - 1)]
                    else:  # all-empty values: every view is empty
                        gathered = np.zeros((total, vw), dtype=np.uint8)
                else:
                    cols = st[:, None] + np.arange(vw, dtype=np.int64)[None, :]
                    gathered = buf.values[
                        src[:total, None], np.clip(cols, 0, width - 1)
                    ]
                out_values[:total] = apply_postops_host(
                    np.where(keep, gathered, 0), ex._view_postops
                )
            out_lengths = np.zeros((rows_out,), dtype=np.int32)
            out_lengths[:total] = ln
            if buf.has_keys():
                out_keys = np.zeros((rows_out, buf.keys.shape[1]), np.uint8)
                out_klens = np.full((rows_out,), -1, np.int32)
                out_keys[:total] = buf.keys[src[:total]]
                out_klens[:total] = buf.key_lengths[src[:total]]
            else:
                out_keys = np.zeros((rows_out, 1), np.uint8)
                out_klens = np.full((rows_out,), -1, np.int32)
        elif ex._int_output:
            windowed = bool(ex.stages[-1].window_ms)
            groups = [self._shard_slices(packed["agg_int"], counts)]
            if windowed:
                groups.append(self._shard_slices(packed["agg_win"], counts))
            src, got = _fetch_all(*groups)
            ex._count_down_variant(None)
            ints = self._concat_counts(got[0], counts).astype(np.int64)
            wins = (
                self._concat_counts(got[1], counts).astype(np.int64)
                if windowed
                else None
            )
            out_values, out_lengths, out_keys, out_klens = (
                ex._int_output_columns(buf, ints, wins, src, rows_out, total)
            )
        else:
            vw = min(
                ex._pad_slice(max(int(hdrs[:, 1].max()), 1)),
                packed["values"].shape[1],
            )
            kw = min(
                ex._pad_slice(max(int(hdrs[:, 2].max()), 1)),
                packed["keys"].shape[1],
            )
            src, got = _fetch_all(
                self._shard_slices(packed["values"], counts, vw),
                self._shard_slices(
                    ex._narrow_static(
                        packed["lengths"], packed["values"].shape[1] + 1
                    ),
                    counts,
                ),
                self._shard_slices(packed["keys"], counts, kw),
                self._shard_slices(packed["key_lengths"], counts),
            )
            # sharded byte-mode still ships the padded matrix (result
            # compaction covers the single-device byte path); count it
            # honestly so the preflight differential stays exact
            TELEMETRY.add_link_variant("down-raw")
            out_values = np.zeros((rows_out, vw), np.uint8)
            out_values[:total] = self._concat_counts(got[0], counts)
            out_lengths = np.zeros((rows_out,), np.int32)
            out_lengths[:total] = self._concat_counts(got[1], counts)
            out_keys = np.zeros((rows_out, kw), np.uint8)
            out_keys[:total] = self._concat_counts(got[2], counts)
            out_klens = np.full((rows_out,), -1, np.int32)
            out_klens[:total] = self._concat_counts(got[3], counts)

        out_off = np.zeros((rows_out,), np.int32)
        out_ts = np.zeros((rows_out,), np.int64)
        src_c = np.clip(src[:total], 0, buf.offset_deltas.shape[0] - 1)
        if ex._fanout:
            # fan-out outputs are "fresh": zero relative to their source
            # record's batch, or the broker's batch-rebase columns
            if buf.fresh_offset_deltas is not None:
                out_off[:total] = buf.fresh_offset_deltas[src_c]
            if buf.fresh_timestamp_deltas is not None:
                out_ts[:total] = buf.fresh_timestamp_deltas[src_c]
        else:
            out_off[:total] = buf.offset_deltas[src_c]
            out_ts[:total] = buf.timestamp_deltas[src_c]

        # commit carries: host mirror stays authoritative across calls
        if ex.agg_configs:
            hostc = jax.device_get(new_carries)
            ex.carries = [(int(a), int(w), bool(h)) for a, w, h in hostc]
            ex._device_carries = None
            ex._sync_instances()

        if span is not None:
            t_end = time.perf_counter()
            wait = 0.0
            if span.ready_t is not None and span.ready_t > t_f0:
                wait = span.ready_t - t_f0
            span.add(
                "fetch", (t_end - t_f0) - wait - (span.phase("d2h") - d2h0)
            )
            # input-record semantic, matching the single-device path
            TELEMETRY.end_batch(span, records=buf.count)

        return RecordBuffer(
            values=out_values,
            lengths=out_lengths,
            keys=out_keys,
            key_lengths=out_klens,
            offset_deltas=out_off,
            timestamp_deltas=out_ts,
            count=total,
            base_offset=buf.base_offset,
            base_timestamp=buf.base_timestamp,
        )

    def process_buffer(self, buf: RecordBuffer) -> RecordBuffer:
        return self.finish_buffer(buf, self.dispatch_buffer(buf))
