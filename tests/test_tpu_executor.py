"""TPU backend vs python backend: golden-output equivalence.

The §4(b)-style gate from SURVEY.md: the same chain on both engines must
produce byte-identical outputs on the baseline configs.
"""

import jax
import numpy as np
import pytest

from fluvio_tpu.models import lookup
from fluvio_tpu.protocol.record import Record
from fluvio_tpu.smartengine import SmartEngine, SmartModuleConfig
from fluvio_tpu.smartengine.engine import EngineError
from fluvio_tpu.smartmodule import SmartModuleInput


def build(backend, *mods):
    b = SmartEngine(backend=backend).builder()
    for module, config in mods:
        b.add_smart_module(config, module)
    return b.initialize()


def run_both(mods, records_fn):
    """Build both backends fresh and feed identical inputs; compare."""
    py = build("python", *mods)
    tpu = build("tpu", *mods)
    assert tpu.backend_in_use == "tpu"
    outs = []
    for records, base_offset, base_ts in records_fn():
        inp1 = SmartModuleInput.from_records(records, base_offset, base_ts)
        records2 = [
            Record(
                value=r.value, key=r.key,
                offset_delta=r.offset_delta, timestamp_delta=r.timestamp_delta,
            )
            for r in records
        ]
        inp2 = SmartModuleInput.from_records(records2, base_offset, base_ts)
        out_py = py.process(inp1)
        out_tpu = tpu.process(inp2)
        assert out_py.error is None and out_tpu.error is None
        got_py = [
            (r.key, r.value, r.offset_delta, r.timestamp_delta)
            for r in out_py.successes
        ]
        got_tpu = [
            (r.key, r.value, r.offset_delta, r.timestamp_delta)
            for r in out_tpu.successes
        ]
        assert got_py == got_tpu
        outs.append(got_py)
    return outs


def recs(*values, deltas=None):
    records = [Record(value=v) for v in values]
    for i, r in enumerate(records):
        r.offset_delta = i
        if deltas:
            r.timestamp_delta = deltas[i]
    return records


class TestEquivalence:
    def test_regex_filter(self):
        def gen():
            yield recs(b"apple", b"banana", b"avocado", b"cherry"), 0, -1

        outs = run_both(
            [(lookup("regex-filter"), SmartModuleConfig(params={"regex": "^a"}))], gen
        )
        assert [v for (_, v, _, _) in outs[0]] == [b"apple", b"avocado"]

    def test_regex_filter_json_map_chain(self):
        """The north-star chain (baseline config #1+#2)."""

        def gen():
            yield recs(
                b'{"name":"fluvio","n":1}',
                b'{"name":"kafka","n":2}',
                b'{"name":"fluvio-tpu","n":3}',
                b"not json at all",
            ), 100, 5000

        outs = run_both(
            [
                (lookup("regex-filter"), SmartModuleConfig(params={"regex": "fluvio"})),
                (lookup("json-map"), SmartModuleConfig(params={"field": "name"})),
            ],
            gen,
        )
        assert [v for (_, v, _, _) in outs[0]] == [b"FLUVIO", b"FLUVIO-TPU"]
        assert [d for (_, _, d, _) in outs[0]] == [0, 2]  # offsets preserved

    def test_aggregate_sum_across_calls(self):
        def gen():
            yield recs(b"1", b"2", b"3"), 0, -1
            yield recs(b"10", b"-4"), 3, -1

        outs = run_both([(lookup("aggregate-sum"), SmartModuleConfig())], gen)
        assert [v for (_, v, _, _) in outs[0]] == [b"1", b"3", b"6"]
        assert [v for (_, v, _, _) in outs[1]] == [b"16", b"12"]

    def test_aggregate_with_seed(self):
        def gen():
            yield recs(b"5"), 0, -1

        outs = run_both(
            [(lookup("aggregate-sum"), SmartModuleConfig(initial_data=b"100"))], gen
        )
        assert [v for (_, v, _, _) in outs[0]] == [b"105"]

    def test_filter_then_aggregate(self):
        def gen():
            yield recs(b"keep 1", b"drop 2", b"keep 3"), 0, -1

        outs = run_both(
            [
                (lookup("regex-filter"), SmartModuleConfig(params={"regex": "keep"})),
                (lookup("aggregate-count"), SmartModuleConfig()),
            ],
            gen,
        )
        assert [v for (_, v, _, _) in outs[0]] == [b"1", b"2"]

    def test_word_count(self):
        def gen():
            yield recs(b"hello world", b"", b"a b  c"), 0, -1

        outs = run_both([(lookup("word-count"), SmartModuleConfig())], gen)
        assert [v for (_, v, _, _) in outs[0]] == [b"2", b"2", b"5"]

    def test_windowed_sum(self):
        def gen():
            yield recs(
                b"1", b"2", b"3", b"4", deltas=[0, 500, 1000, 1500]
            ), 0, 10_000
            # second slab continues the last window then opens a new one
            yield recs(b"5", b"6", deltas=[1600, 2100]), 4, 10_000

        outs = run_both(
            [(lookup("windowed-sum"), SmartModuleConfig(params={"window_ms": "1000"}))],
            gen,
        )
        assert [(k, v) for (k, v, _, _) in outs[0]] == [
            (b"10000", b"1"),
            (b"10000", b"3"),
            (b"11000", b"3"),
            (b"11000", b"7"),
        ]
        assert [(k, v) for (k, v, _, _) in outs[1]] == [
            (b"11000", b"12"),
            (b"12000", b"6"),
        ]

    def test_aggregate_max_min(self):
        def gen():
            yield recs(b"5", b"3", b"9", b"7"), 0, -1

        outs = run_both([(lookup("aggregate-max"), SmartModuleConfig())], gen)
        assert [v for (_, v, _, _) in outs[0]] == [b"5", b"5", b"9", b"9"]

    def test_keys_preserved_through_filter(self):
        def gen():
            records = [
                Record(value=b"al", key=b"k0"),
                Record(value=b"bx", key=None),
                Record(value=b"ay", key=b"k2"),
            ]
            for i, r in enumerate(records):
                r.offset_delta = i
            yield records, 0, -1

        outs = run_both(
            [(lookup("regex-filter"), SmartModuleConfig(params={"regex": "^a"}))], gen
        )
        assert [(k, v) for (k, v, _, _) in outs[0]] == [(b"k0", b"al"), (b"k2", b"ay")]

    def test_fuzz_northstar_chain(self):
        rng = np.random.default_rng(3)
        names = ["fluvio", "kafka", "pulsar", "fluvio-tpu", "x"]

        def gen():
            for base in (0, 1000):
                records = []
                for i in range(rng.integers(5, 40)):
                    name = names[rng.integers(0, len(names))]
                    n = rng.integers(0, 100)
                    records.append(Record(value=f'{{"name":"{name}","n":{n}}}'.encode()))
                for i, r in enumerate(records):
                    r.offset_delta = i
                yield records, base, -1

        run_both(
            [
                (lookup("regex-filter"), SmartModuleConfig(params={"regex": "fluvio"})),
                (lookup("json-map"), SmartModuleConfig(params={"field": "n"})),
            ],
            gen,
        )


class TestBackendSelection:
    def test_tpu_refuses_hook_only_module(self):
        src = "@smartmodule.filter\ndef f(record):\n    return True\n"
        b = SmartEngine(backend="tpu").builder()
        b.add_smart_module(SmartModuleConfig(), src)
        with pytest.raises(EngineError):
            b.initialize()

    def test_auto_falls_back_to_python(self):
        src = "@smartmodule.filter\ndef f(record):\n    return True\n"
        b = SmartEngine(backend="auto").builder()
        b.add_smart_module(SmartModuleConfig(), src)
        chain = b.initialize()
        assert chain.backend_in_use == "python"

    def test_auto_uses_tpu_for_dsl_chain(self):
        b = SmartEngine(backend="auto").builder()
        b.add_smart_module(
            SmartModuleConfig(params={"regex": "x"}), lookup("regex-filter")
        )
        chain = b.initialize()
        assert chain.backend_in_use == "tpu"

    def test_unsupported_regex_falls_back(self):
        """Backreferences can't become DFAs: auto skips the TPU backend
        and lands on a host engine (native when a toolchain exists)."""
        from fluvio_tpu.protocol.record import Record
        from fluvio_tpu.smartmodule.types import SmartModuleInput

        b = SmartEngine(backend="auto").builder()
        b.add_smart_module(
            SmartModuleConfig(params={"regex": r"(a)\1"}), lookup("regex-filter")
        )
        chain = b.initialize()
        assert chain.backend_in_use in ("python", "native")
        out = chain.process(
            SmartModuleInput.from_records(
                [Record(value=b"has aa here"), Record(value=b"only a")]
            )
        )
        assert [r.value for r in out.successes] == [b"has aa here"]


class TestWidthBuckets:
    """Value-matrix width buckets: padding is scan compute, so widths
    above 128 bucket at pow2/8 granularity (review round 4 weak #3 — a
    300 B corpus runs 320 scan steps, not 512)."""

    def test_bucket_width_values(self):
        from fluvio_tpu.smartengine.tpu.buffer import bucket_width

        assert bucket_width(0) == 32
        assert bucket_width(33) == 64
        assert bucket_width(128) == 128
        assert bucket_width(129) == 160
        assert bucket_width(310) == 320
        assert bucket_width(505) == 512
        assert bucket_width(513) == 640

    def test_bucket_width_invariants(self):
        from fluvio_tpu.smartengine.tpu.buffer import bucket_width

        prev = 0
        for n in range(0, 5000, 7):
            w = bucket_width(n)
            assert w >= max(n, 32)
            assert w % 32 == 0 or w < 128
            assert w >= prev  # monotone: bigger records never shrink
            prev = w

    def test_wide_corpus_chain_equivalence(self):
        """300 B records (uint16 descriptor tier + non-pow2 width) stay
        byte-equal to the interpreter through the full chain."""
        from fluvio_tpu.protocol.record import Record
        from fluvio_tpu.smartmodule.types import SmartModuleInput

        pad = "p" * 240
        values = [
            f'{{"name":"fluvio-{i}","pad":"{pad}","n":{i}}}'.encode()
            for i in range(50)
        ]

        def run(backend):
            b = SmartEngine(backend=backend).builder()
            b.add_smart_module(
                SmartModuleConfig(params={"regex": "fluvio"}),
                lookup("regex-filter"),
            )
            b.add_smart_module(
                SmartModuleConfig(params={"field": "name"}), lookup("json-map")
            )
            chain = b.initialize()
            out = chain.process(
                SmartModuleInput.from_records(
                    [Record(value=v) for v in values]
                )
            )
            assert out.error is None
            return [r.value for r in out.successes]

        got = run("tpu")
        assert got == run("python")
        assert len(got) == 50


class TestDispatchPrefetch:
    """Dispatch-time speculative D2H (the link-RTT diet).

    `dispatch_buffer` starts the header/mask copies and — once two
    consecutive batches agree on a survivor bucket — the viewable
    descriptor slices, speculatively. A stream whose survivor counts
    shift buckets mid-flight must stay byte-correct through both the
    hit and the miss path, and the miss must charge the wasted bytes
    to the D2H counter.
    """

    def _bufs(self, counts):
        from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer

        out = []
        for match_n in counts:
            records = [
                Record(
                    value=(
                        b'{"name":"fluvio-%d"}' % i
                        if i < match_n
                        else b'{"name":"drop-%d"}' % i
                    )
                )
                for i in range(256)
            ]
            for i, r in enumerate(records):
                r.offset_delta = i
            out.append(RecordBuffer.from_records(records))
        return out

    def _chain(self, backend):
        # filter + span-map: descriptor speculation only exists for
        # view chains with real descriptors (a filter-only chain rides
        # the identity path, where the mask is the whole download)
        return build(
            backend,
            (lookup("regex-filter"), SmartModuleConfig(params={"regex": "fluvio"})),
            (lookup("json-map"), SmartModuleConfig(params={"field": "name"})),
        )

    def test_stream_correct_across_bucket_shift(self):
        # the pipelined stream dispatches one batch ahead of the
        # finishes that feed the bucket history, so arming lags one
        # batch: the 40-run hits from its 4th dispatch, the 40->200
        # shift misses twice (stale guess, then disagreeing history),
        # and the 200-run re-arms and hits at its 4th batch
        counts = [40, 40, 40, 40, 40, 200, 200, 200, 200]
        tpu = self._chain("tpu").tpu_chain
        piped = [
            [r.value for r in out.to_records()]
            for out in tpu.process_stream(iter(self._bufs(counts)))
        ]
        py = self._chain("python")
        for vals, buf in zip(piped, self._bufs(counts)):
            out = py.process(
                SmartModuleInput.from_records(buf.to_records())
            )
            assert vals == [r.value for r in out.successes]

    def test_discarded_dispatch_is_settled_before_exit(self, monkeypatch):
        """A discarded dispatch's prefetch copies have no fetch to wait
        for them: the discard registers ONE exit hook (idempotent), and
        the hook leaves nothing of the dispatch in flight (on the chip a
        process that exited under them died with SIGSEGV, PR 33)."""
        import atexit

        calls = []
        monkeypatch.setattr(atexit, "register", lambda f: calls.append(("reg", f)))
        monkeypatch.setattr(atexit, "unregister", lambda f: calls.append(("un", f)))
        tpu = self._chain("tpu").tpu_chain
        (b40,) = self._bufs([40])
        hook = tpu._settle_device_at_exit
        for _ in range(2):
            h = tpu.dispatch_buffer(b40)
            tpu.discard_dispatch(h)
        assert calls == [("un", hook), ("reg", hook)] * 2
        hook()
        arrays = [
            a for a in jax.tree_util.tree_leaves((h[1], h[2]))
            if isinstance(a, jax.Array)
        ]
        assert arrays and all(a.is_ready() for a in arrays)
        # the stream goes on: the discard left the executor usable
        out = tpu.finish_buffer(b40, tpu.dispatch_buffer(b40))
        assert out.count == 40

    def test_spec_arms_hits_and_charges_misses(self):
        tpu = self._chain("tpu").tpu_chain
        b40, b200 = self._bufs([40, 200])

        h = tpu.dispatch_buffer(b40)
        assert "view" not in h[3]  # cold: no guess yet
        tpu.finish_buffer(b40, h)
        h = tpu.dispatch_buffer(b40)
        assert "view" not in h[3]  # one observation: not armed yet
        tpu.finish_buffer(b40, h)

        h = tpu.dispatch_buffer(b40)
        assert "view" in h[3]  # two agreeing buckets: armed
        rows_guess = h[3]["view"][0]
        hit_spec = h[3]["view"]
        d2h_before = tpu.d2h_bytes_total
        out = tpu.finish_buffer(b40, h)  # hit: same bucket
        # the hit path must return the right BYTES (the prefetched
        # descriptor slices drive the host-side value rebuild) ...
        assert [r.value for r in out.to_records()] == [
            b"FLUVIO-%d" % i for i in range(40)  # json-map uppercases
        ]
        # ... and download the prefetched slices exactly once
        hit_delta = tpu.d2h_bytes_total - d2h_before
        assert hit_delta >= hit_spec[1].nbytes + hit_spec[2].nbytes
        assert hit_delta < 2 * (hit_spec[1].nbytes + hit_spec[2].nbytes) + 4096

        h = tpu.dispatch_buffer(b200)
        assert "view" in h[3]
        spec = h[3]["view"]
        d2h_before = tpu.d2h_bytes_total
        tpu.finish_buffer(b200, h)  # miss: bucket shifted
        wasted = spec[1].nbytes + spec[2].nbytes
        assert tpu.d2h_bytes_total - d2h_before >= wasted
        assert tpu._spec_rows != rows_guess


class TestTransferGuardArm:
    """ISSUE-7 tier-1 arm: with ``FLUVIO_TRANSFER_GUARD=disallow`` the
    executor runs every dispatch-side region under
    ``jax.transfer_guard_device_to_host("disallow")`` while the
    intentional fetch/d2h seam stays on an explicit allow scope. On an
    accelerator an implicit D2H raises at the offending line; on the
    host-resident CPU backend the scopes are structurally exercised and
    these tests pin the seam selection itself."""

    def test_unarmed_seams_are_shared_nullcontext(self, monkeypatch):
        from fluvio_tpu.smartengine.tpu import executor as ex

        monkeypatch.delenv("FLUVIO_TRANSFER_GUARD", raising=False)
        assert ex.transfer_guard_dispatch() is ex._NULL_CTX
        assert ex.transfer_guard_fetch() is ex._NULL_CTX
        # explicit off-spellings disarm BOTH seams consistently
        for off in ("0", "off", "none", "allow", " OFF "):
            monkeypatch.setenv("FLUVIO_TRANSFER_GUARD", off)
            assert ex.transfer_guard_dispatch() is ex._NULL_CTX
            assert ex.transfer_guard_fetch() is ex._NULL_CTX

    def test_invalid_mode_rejected_loudly(self, monkeypatch):
        """A typo'd arm must not silently half-arm the guard (dispatch
        unguarded while fetch enters the allow scope)."""
        from fluvio_tpu.smartengine.tpu import executor as ex

        monkeypatch.setenv("FLUVIO_TRANSFER_GUARD", "disalow")
        with pytest.raises(ValueError, match="FLUVIO_TRANSFER_GUARD"):
            ex.transfer_guard_dispatch()
        with pytest.raises(ValueError, match="FLUVIO_TRANSFER_GUARD"):
            ex.transfer_guard_fetch()

    def test_armed_scopes_select_guard_modes(self, monkeypatch):
        from jax._src import config as jcfg

        from fluvio_tpu.smartengine.tpu import executor as ex

        monkeypatch.setenv("FLUVIO_TRANSFER_GUARD", "disallow")
        with ex.transfer_guard_dispatch():
            assert jcfg.transfer_guard_device_to_host.value == "disallow"
            # the allowlist: the fetch seam re-opens D2H even inside an
            # armed dispatch scope (and under a process-global arm)
            with ex.transfer_guard_fetch():
                assert jcfg.transfer_guard_device_to_host.value == "allow"
            assert jcfg.transfer_guard_device_to_host.value == "disallow"

    def _spy_seams(self, monkeypatch):
        """Record the ACTIVE guard mode at entry to the real dispatch
        and fetch bodies during live traffic."""
        from jax._src import config as jcfg

        from fluvio_tpu.smartengine.tpu.executor import TpuChainExecutor

        seen = {"dispatch": set(), "fetch": set()}
        orig_dispatch = TpuChainExecutor._dispatch_inner
        orig_fetch = TpuChainExecutor._fetch_inner

        def spy_dispatch(self, *a, **k):
            seen["dispatch"].add(jcfg.transfer_guard_device_to_host.value)
            return orig_dispatch(self, *a, **k)

        def spy_fetch(self, *a, **k):
            seen["fetch"].add(jcfg.transfer_guard_device_to_host.value)
            return orig_fetch(self, *a, **k)

        monkeypatch.setattr(TpuChainExecutor, "_dispatch_inner", spy_dispatch)
        monkeypatch.setattr(TpuChainExecutor, "_fetch_inner", spy_fetch)
        return seen

    def test_fused_path_clean_under_disallow(self, monkeypatch):
        monkeypatch.setenv("FLUVIO_TRANSFER_GUARD", "disallow")
        seen = self._spy_seams(monkeypatch)
        mods = [
            (lookup("regex-filter"), SmartModuleConfig(params={"regex": "fluvio"})),
            (lookup("json-map"), SmartModuleConfig(params={"field": "name"})),
        ]

        def gen():
            yield recs(
                b'{"name":"fluvio-a","n":1}',
                b'{"name":"kafka-b","n":2}',
                b'{"name":"fluvio-c","n":3}',
            ), 0, 0

        run_both([(m, c) for m, c in mods], gen)
        assert seen["dispatch"] == {"disallow"}
        assert seen["fetch"] == {"allow"}

    def test_striped_path_clean_under_disallow(self, monkeypatch):
        """The striped lowering's dispatch runs under the same guard
        scope (stripe gates forced low so a ~300 B corpus stripes)."""
        monkeypatch.setenv("FLUVIO_TRANSFER_GUARD", "disallow")
        monkeypatch.setenv("FLUVIO_STRIPE_THRESHOLD", "64")
        monkeypatch.setenv("FLUVIO_STRIPE_WIDTH", "64")
        monkeypatch.setenv("FLUVIO_STRIPE_OVERLAP", "16")
        seen = self._spy_seams(monkeypatch)
        pad = "p" * 240
        values = [
            f'{{"name":"fluvio-{i}","pad":"{pad}","n":{i}}}'.encode()
            for i in range(40)
        ]

        def run(backend):
            chain = build(
                backend,
                (lookup("regex-filter"),
                 SmartModuleConfig(params={"regex": "fluvio"})),
                (lookup("json-map"),
                 SmartModuleConfig(params={"field": "name"})),
            )
            out = chain.process(
                SmartModuleInput.from_records(
                    [Record(value=v) for v in values]
                )
            )
            assert out.error is None
            return [r.value for r in out.successes]

        tpu_chain = build(
            "tpu",
            (lookup("regex-filter"),
             SmartModuleConfig(params={"regex": "fluvio"})),
            (lookup("json-map"),
             SmartModuleConfig(params={"field": "name"})),
        )
        assert tpu_chain.tpu_chain._striped_chain() is not None
        got = run("tpu")
        assert got == run("python")
        assert len(got) == 40
        assert seen["dispatch"] == {"disallow"}
        assert seen["fetch"] == {"allow"}

    def _spy_sharded_seams(self, monkeypatch):
        """Record the ACTIVE guard mode inside the sharded delegate's
        dispatch and finish bodies. The dispatch spy hooks
        `_dispatch_buffer_inner` — `dispatch_buffer` enters the guard
        scope itself, so the mode INSIDE the body is the invariant,
        whatever scope the caller was in."""
        from jax._src import config as jcfg

        from fluvio_tpu.parallel.sharded import ShardedChainExecutor

        seen = {"dispatch": [], "finish": []}
        orig_dispatch = ShardedChainExecutor._dispatch_buffer_inner
        orig_finish = ShardedChainExecutor.finish_buffer

        def spy_dispatch(self, *a, **k):
            seen["dispatch"].append(jcfg.transfer_guard_device_to_host.value)
            return orig_dispatch(self, *a, **k)

        def spy_finish(self, *a, **k):
            seen["finish"].append(jcfg.transfer_guard_device_to_host.value)
            return orig_finish(self, *a, **k)

        monkeypatch.setattr(
            ShardedChainExecutor, "_dispatch_buffer_inner", spy_dispatch
        )
        monkeypatch.setattr(ShardedChainExecutor, "finish_buffer", spy_finish)
        return seen

    @pytest.mark.skipif(
        len(jax.devices()) < 8, reason="needs 8 virtual devices"
    )
    def test_sharded_path_clean_under_disallow(self, monkeypatch):
        """The sharded delegate's dispatch runs under the dispatch
        guard; only the finish/download half sees the allow seam."""
        monkeypatch.setenv("FLUVIO_TRANSFER_GUARD", "disallow")
        seen = self._spy_sharded_seams(monkeypatch)
        chain = build(
            "tpu",
            (lookup("regex-filter"),
             SmartModuleConfig(params={"regex": "fluvio"})),
        )
        ex = chain.tpu_chain
        ex.enable_sharded(8)
        values = [
            (f'fluvio-{i}' if i % 2 else f'kafka-{i}').encode()
            for i in range(64)
        ]
        inp = SmartModuleInput.from_records([Record(value=v) for v in values])
        out = chain.process(inp)
        assert out.error is None and len(out.successes) == 32
        assert set(seen["dispatch"]) == {"disallow"}
        assert set(seen["finish"]) == {"allow"}

    @pytest.mark.skipif(
        len(jax.devices()) < 8, reason="needs 8 virtual devices"
    )
    def test_sharded_direct_process_buffer_guarded(self, monkeypatch):
        """Regression: `ShardedChainExecutor.process_buffer` drives
        `dispatch_buffer` with no executor delegation in between — the
        guard scope lives inside `dispatch_buffer`, so the direct
        entry point dispatches guarded too."""
        from fluvio_tpu.smartengine.tpu.buffer import RecordBuffer

        monkeypatch.setenv("FLUVIO_TRANSFER_GUARD", "disallow")
        seen = self._spy_sharded_seams(monkeypatch)
        chain = build(
            "tpu",
            (lookup("regex-filter"),
             SmartModuleConfig(params={"regex": "fluvio"})),
        )
        ex = chain.tpu_chain
        ex.enable_sharded(8)
        records = [
            Record(value=(f'fluvio-{i}' if i % 2 else f'kafka-{i}').encode())
            for i in range(64)
        ]
        for i, r in enumerate(records):
            r.offset_delta = i
        buf = RecordBuffer.from_records(
            records, base_offset=0, base_timestamp=1000
        )
        out = ex._sharded.process_buffer(buf)
        assert len(out.to_records()) == 32
        assert set(seen["dispatch"]) == {"disallow"}

    @pytest.mark.skipif(
        len(jax.devices()) < 8, reason="needs 8 virtual devices"
    )
    def test_sharded_retry_redispatch_stays_guarded(self, monkeypatch):
        """Regression: the transient-retry re-dispatch inside
        `_finish_sharded_inner` fires from within the fetch ALLOW scope
        — it must re-enter the dispatch guard, not inherit the
        allowlist (an implicit D2H during a retry is exactly the class
        the arm exists to reject)."""
        from fluvio_tpu.resilience import faults

        monkeypatch.setenv("FLUVIO_TRANSFER_GUARD", "disallow")
        monkeypatch.setenv("FLUVIO_RETRY_BASE_MS", "0")
        seen = self._spy_sharded_seams(monkeypatch)
        chain = build(
            "tpu",
            (lookup("regex-filter"),
             SmartModuleConfig(params={"regex": "fluvio"})),
        )
        ex = chain.tpu_chain
        ex.enable_sharded(8)
        faults.FAULTS.inject("device", first=1)
        try:
            inp = SmartModuleInput.from_records(
                [Record(value=b"fluvio-x")] * 64
            )
            out = chain.process(inp)
        finally:
            faults.FAULTS.clear()
        assert out.error is None and len(out.successes) == 64
        # initial dispatch + the retry re-dispatch: BOTH under disallow
        assert len(seen["dispatch"]) == 2
        assert set(seen["dispatch"]) == {"disallow"}
        # the failed finish attempt and its retry both ran on the seam
        assert len(seen["finish"]) == 2
        assert set(seen["finish"]) == {"allow"}
