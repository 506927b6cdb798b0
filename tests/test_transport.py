"""Multi-host transport backends (ISSUE 5).

The contract under test: a :class:`~repro.core.transport.RemoteUnit`
driving a :class:`~repro.core.transport.RemoteWorker` across a message
transport behaves like any other backend unit — and keeps behaving like
one when the *medium* misbehaves:

* completions pumped back over the transport land on the local
  ``CompletionBus`` and tile the space exactly,
* the seq/retransmit/dedup protocol survives seeded drop / delay /
  duplicate / reorder injection (``FlakyTransport``) with **exact-once
  work-function side effects** — parity with inline execution — across
  ≥20 random seeds, with monotone event times,
* a definitive connection loss requeues the in-flight chunk to the
  survivors (an ``action="lost"`` event) instead of hanging or failing
  the run,
* real ``SocketTransport`` worker *subprocesses* behind a
  ``ShardedSpace(placement=...)`` produce byte-identical results versus
  ``backend="inline"`` (the ISSUE's acceptance line),
* ``RunReport.dispatch_latency`` is split: ``wire_latency`` carries the
  send→remote-execution-start component for remote units.

Loopback tests pass frames by reference (shared side-effect ledgers);
socket tests exercise the length-prefixed pickle codec and cross-process
execution for real.  CI's ``transport`` job runs this module under the
hang-killing ``tools/run_with_timeout.py``.
"""

import os
import socket
import threading
import time
from collections import Counter

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # CI container has no hypothesis; use the vendored shim
    from _propcheck import given, settings, strategies as st

from repro.core import (
    ElasticSchedule,
    FlakyTransport,
    HeteroRuntime,
    LoopbackTransport,
    RemoteUnit,
    RemoteWorker,
    ShardedSpace,
    SocketTransport,
    TransportClosed,
    TransportError,
    WorkerKind,
    WorkerServer,
)
from repro.core.backends import CompletionBus, make_backend
from repro.core.runtime import POLICIES
from repro.core.scheduler import Chunk
from repro.core.transport import (
    AUTO_BATCH_MAX,
    FrameDecoder,
    SleepWork,
    encode_frame,
    spawn_worker,
)


def assert_exact_tiling(spans, n_items):
    assert spans, "no chunks completed"
    assert spans[0][0] == 0
    assert spans[-1][1] == n_items
    for (a, b), (c, d) in zip(spans, spans[1:]):
        assert b == c, f"gap or overlap at {b}:{c}"


class Recorder:
    """Thread-safe exact-once ledger (shared by reference over loopback)."""

    def __init__(self, per_item_sleep=0.0):
        self.lock = threading.Lock()
        self.counts = Counter()
        self.per_item_sleep = per_item_sleep

    def __call__(self, chunk):
        if self.per_item_sleep:
            time.sleep(chunk.size * self.per_item_sleep)
        with self.lock:
            self.counts.update(chunk.indices())

    def assert_exactly_once(self, n_items):
        assert set(self.counts) == set(range(n_items)), (
            f"missing {sorted(set(range(n_items)) - set(self.counts))[:5]}..."
        )
        dupes = {i: c for i, c in self.counts.items() if c != 1}
        assert not dupes, f"indices executed more than once: {dupes}"


def start_loopback_worker(*, flaky_seed=None, **faults):
    """(client endpoint, worker, serve thread) over an in-process pair."""
    client_end, worker_end = LoopbackTransport.pair()
    client_side, worker_side = client_end, worker_end
    if flaky_seed is not None:
        client_side = FlakyTransport(client_end, seed=flaky_seed, **faults)
        worker_side = FlakyTransport(worker_end, seed=flaky_seed + 1, **faults)
    worker = RemoteWorker(worker_side, poll_interval=0.05)
    t = threading.Thread(target=worker.serve, daemon=True)
    t.start()
    return client_side, worker, t


def loopback_unit(name, *, flaky_seed=None, retry_interval=0.02,
                  max_retries=600, batch_frames=1, fn_cache=True, **faults):
    client_side, worker, _t = start_loopback_worker(
        flaky_seed=flaky_seed, **faults)
    return RemoteUnit(name, transport=client_side,
                      retry_interval=retry_interval, max_retries=max_retries,
                      batch_frames=batch_frames, fn_cache=fn_cache)


class FrameTap:
    """Pass-through transport recording every frame sent through it."""

    def __init__(self, inner):
        self.inner = inner
        self.sent = []
        self._lock = threading.Lock()

    def send(self, frame):
        with self._lock:
            self.sent.append(frame)
        self._forward(frame)

    def _forward(self, frame):
        """Override to drop/mangle frames (still recorded in .sent)."""
        self.inner.send(frame)

    def recv(self, timeout=None):
        return self.inner.recv(timeout)

    def close(self):
        self.inner.close()

    @property
    def closed(self):
        return self.inner.closed

    def kinds(self):
        with self._lock:
            return Counter(f.get("kind") for f in self.sent)

    def frames(self, kind):
        with self._lock:
            return [f for f in self.sent if f.get("kind") == kind]


def tapped_loopback_unit(name, *, batch_frames=1, fn_cache=True,
                         tap_cls=FrameTap, **kw):
    """A clean-medium loopback unit whose client->worker frames are
    recorded in (and optionally filtered by) the returned FrameTap."""
    client_end, worker_end = LoopbackTransport.pair()
    worker = RemoteWorker(worker_end, poll_interval=0.02)
    threading.Thread(target=worker.serve, daemon=True).start()
    tap = tap_cls(client_end)
    unit = RemoteUnit(name, transport=tap, retry_interval=0.05,
                      max_retries=200, batch_frames=batch_frames,
                      fn_cache=fn_cache, **kw)
    return unit, tap, worker


# ---------------------------------------------------------------------------
# frame codec
# ---------------------------------------------------------------------------
class TestFrameCodec:
    def test_roundtrip_single_frame(self):
        frame = {"kind": "submit", "seq": 7, "chunk": Chunk(3, 9, "u0"),
                 "payload": list(range(10))}
        dec = FrameDecoder()
        (out,) = dec.feed(encode_frame(frame))
        assert out == frame

    def test_incremental_feed_any_segmentation(self):
        frames = [{"kind": "done", "seq": i, "blob": b"x" * (i * 13)}
                  for i in range(6)]
        stream = b"".join(encode_frame(f) for f in frames)
        for step in (1, 2, 3, 5, 7, 64, len(stream)):
            dec = FrameDecoder()
            out = []
            for i in range(0, len(stream), step):
                out.extend(dec.feed(stream[i:i + step]))
            assert out == frames, f"segmentation step={step} corrupted frames"

    def test_corrupt_header_raises(self):
        dec = FrameDecoder()
        with pytest.raises(TransportError, match="corrupt"):
            dec.feed(b"\xff\xff\xff\xff garbage")

    def test_unpicklable_payload_becomes_poison_frame(self):
        # a payload that pickled fine on the sender but cannot unpickle
        # here (e.g. a work_fn from a module this process cannot import)
        # must not kill the session: the decoder yields an ignorable
        # poison frame and the stream stays aligned for frames after it
        import struct

        good = {"kind": "done", "seq": 1}
        payload = b"cno_such_module_xyz\nGhost\n."  # GLOBAL opcode, bad module
        data = struct.pack(">I", len(payload)) + payload
        dec = FrameDecoder()
        out = dec.feed(data + encode_frame(good))
        assert out[0]["kind"] == "undecodable"
        assert out[1] == good


def _random_batched_frame(rng):
    """A randomized fast-path frame (work_batch / done_batch / singletons)."""
    kind = rng.choice(["work_batch", "done_batch", "submit", "register_fn"])
    frame = {"kind": kind, "unit": f"u{rng.randrange(4)}"}
    if kind in ("work_batch", "submit"):
        frame["floor"] = rng.randrange(64)
    if kind == "register_fn":
        frame["fn_id"] = f"h:{rng.getrandbits(64):016x}"
        frame["fn"] = SleepWork(rng.random() * 1e-6)
        return frame
    items = []
    for i in range(rng.randint(1, 8)):
        start = rng.randrange(1000)
        items.append({
            "seq": rng.randrange(512),
            "chunk": Chunk(start, start + rng.randint(1, 32), frame["unit"]),
            "t_submit": rng.random(),
            "blob": bytes(rng.getrandbits(8)
                          for _ in range(rng.randint(0, 300))),
        })
    if kind == "submit":
        frame.update(items[0])
    else:
        frame["items"] = items
    return frame


class TestBatchedFrameCodecProperty:
    """encode_frame/FrameDecoder on randomized fast-path frames, split
    across arbitrary byte boundaries, with poison recovery mid-batch."""

    @given(seed=st.integers(0, 10**6))
    @settings(max_examples=30, deadline=None)
    def test_roundtrip_any_segmentation(self, seed):
        import random

        rng = random.Random(seed)
        frames = [_random_batched_frame(rng)
                  for _ in range(rng.randint(1, 6))]
        stream = b"".join(encode_frame(f) for f in frames)
        dec = FrameDecoder()
        out, i = [], 0
        while i < len(stream):
            j = min(len(stream), i + rng.randint(1, 17))
            out.extend(dec.feed(stream[i:j]))
            i = j
        assert len(out) == len(frames)
        for got, want in zip(out, frames):
            # SleepWork instances pickle-roundtrip into equal-by-field
            # copies, not identical objects — compare the stable keys
            assert got["kind"] == want["kind"]
            assert {k: v for k, v in got.items() if k != "fn"} == \
                   {k: v for k, v in want.items() if k != "fn"}

    @given(seed=st.integers(0, 10**6))
    @settings(max_examples=20, deadline=None)
    def test_poison_frame_mid_batch_stream_recovers(self, seed):
        import random
        import struct

        rng = random.Random(seed)
        before = [_random_batched_frame(rng)
                  for _ in range(rng.randint(1, 3))]
        after = [_random_batched_frame(rng)
                 for _ in range(rng.randint(1, 3))]
        poison = b"cno_such_module_xyz\nGhost\n."  # GLOBAL opcode, bad module
        stream = (b"".join(encode_frame(f) for f in before)
                  + struct.pack(">I", len(poison)) + poison
                  + b"".join(encode_frame(f) for f in after))
        dec = FrameDecoder()
        out, i = [], 0
        while i < len(stream):
            j = min(len(stream), i + rng.randint(1, 33))
            out.extend(dec.feed(stream[i:j]))
            i = j
        assert len(out) == len(before) + 1 + len(after)
        kinds = [f["kind"] for f in out]
        assert kinds[len(before)] == "undecodable"
        for got, want in zip(out[:len(before)] + out[len(before) + 1:],
                             before + after):
            assert got["kind"] == want["kind"]


# ---------------------------------------------------------------------------
# transports
# ---------------------------------------------------------------------------
class TestLoopbackTransport:
    def test_pair_send_recv_by_reference(self):
        a, b = LoopbackTransport.pair()
        frame = {"kind": "hello", "obj": object()}  # not picklable, fine here
        a.send(frame)
        assert b.recv(timeout=1.0) is frame
        assert b.recv(timeout=0.01) is None

    def test_close_raises_on_both_ends(self):
        a, b = LoopbackTransport.pair()
        a.close()
        with pytest.raises(TransportClosed):
            b.recv(timeout=1.0)
        with pytest.raises(TransportClosed):
            b.send({"kind": "x"})
        with pytest.raises(TransportClosed):
            a.recv(timeout=0.01)


def socket_transport_pair():
    a, b = socket.socketpair()
    return SocketTransport(a), SocketTransport(b)


class TestSocketTransport:
    def test_frames_roundtrip_including_large(self):
        # the 1MB frame overflows the kernel socket buffer, so the sender
        # must run concurrently with the receiver (as it does in real use)
        a, b = socket_transport_pair()
        try:
            frames = [{"kind": "submit", "seq": 0, "chunk": Chunk(0, 4, "u")},
                      {"kind": "done", "seq": 0, "result": b"z" * 1_000_000}]
            sender = threading.Thread(
                target=lambda: [a.send(f) for f in frames], daemon=True)
            sender.start()
            got = [b.recv(timeout=10.0), b.recv(timeout=10.0)]
            sender.join(timeout=10.0)
            assert not sender.is_alive()
            assert got == frames
        finally:
            a.close()
            b.close()

    def test_recv_timeout_returns_none(self):
        a, b = socket_transport_pair()
        try:
            t0 = time.perf_counter()
            assert b.recv(timeout=0.05) is None
            assert time.perf_counter() - t0 < 2.0
        finally:
            a.close()
            b.close()

    def test_peer_close_raises_transport_closed(self):
        a, b = socket_transport_pair()
        a.close()
        with pytest.raises(TransportClosed):
            b.recv(timeout=5.0)

    def test_tcp_connect_against_worker_server(self):
        server = WorkerServer().start()
        try:
            tr = SocketTransport.connect(server.address, timeout=5.0)
            tr.send({"kind": "hello", "unit": "u0", "backend": "inline"})
            frame = tr.recv(timeout=5.0)
            assert frame == {"kind": "ready", "unit": "u0", "platform": "cpu"}
            tr.close()
        finally:
            server.stop()


# ---------------------------------------------------------------------------
# RemoteUnit over loopback: an ordinary backend unit
# ---------------------------------------------------------------------------
class TestRemoteUnitLoopback:
    def _drive(self, unit, chunks, work_fn):
        bus = CompletionBus()
        unit.start(bus)
        try:
            recs = []
            for c in chunks:
                unit.submit(c, work_fn)
                assert bus.wait(timeout=10.0)
                recs.extend(bus.drain())
            return recs
        finally:
            unit.close()

    def test_submit_completes_with_result_and_latency_split(self):
        unit = loopback_unit("u0")
        recs = self._drive(
            unit, [Chunk(0, 4, "u0"), Chunk(4, 9, "u0")],
            lambda c: c.size * 10,
        )
        assert [r.result for r in recs] == [40, 50]
        assert all(r.error is None for r in recs)
        assert len(unit.dispatch_latencies) == 2
        assert len(unit.wire_latencies) == 2
        assert len(unit.local_queue_latencies) == 2
        for total, wire, local in zip(unit.dispatch_latencies,
                                      unit.wire_latencies,
                                      unit.local_queue_latencies):
            assert total >= 0 and wire >= 0 and local >= 0
            # the split re-composes (both components clamp at 0)
            assert total <= wire + local + 1e-6 or total >= 0

    def test_work_runs_on_the_worker_side_thread(self):
        unit = loopback_unit("u0")
        caller = threading.get_ident()
        recs = self._drive(unit, [Chunk(0, 1, "u0")],
                           lambda c: threading.get_ident())
        assert recs[0].result != caller

    def test_work_fn_error_crosses_the_transport(self):
        def boom(c):
            raise ValueError("remote kaput")

        recs = self._drive(loopback_unit("u0"), [Chunk(0, 1, "u0")], boom)
        assert isinstance(recs[0].error, ValueError)

    def test_parallel_for_mixed_remote_and_local(self):
        rec = Recorder(per_item_sleep=2e-5)
        rt = HeteroRuntime()
        rt.register_unit("r0", WorkerKind.CC, work_fn=rec,
                         backend=loopback_unit("r0"))
        rt.register_unit("r1", WorkerKind.CC, work_fn=rec,
                         backend=loopback_unit("r1"))
        rt.register_unit("cc0", WorkerKind.CC, work_fn=rec)
        rep = rt.parallel_for(num_items=300, policy="multidynamic",
                              engine="interrupt", acc_chunk=16)
        assert rep.items == 300
        assert_exact_tiling(rep.coverage, 300)
        rec.assert_exactly_once(300)
        # dispatch latency covers everyone; wire latency only remote units
        assert set(rep.dispatch_latency) == {"r0", "r1", "cc0"}
        assert set(rep.wire_latency) <= {"r0", "r1"}
        assert rep.wire_latency, "remote units must report a wire component"
        for u, wire in rep.wire_latency.items():
            assert 0.0 <= wire <= rep.dispatch_latency[u] + 1e-6

    def test_work_fn_error_fails_parallel_for(self):
        def boom(c):
            raise ValueError("chunk exploded remotely")

        rt = HeteroRuntime()
        rt.register_unit("r0", WorkerKind.CC, work_fn=boom,
                         backend=loopback_unit("r0"))
        with pytest.raises(ValueError, match="exploded remotely"):
            rt.parallel_for(num_items=50, engine="interrupt", acc_chunk=8)

    def test_long_chunk_is_not_mistaken_for_a_lost_worker(self):
        # execution time (400ms) far exceeds the retransmit budget
        # (5 x 10ms): the worker's busy answers must keep the unit alive —
        # the budget bounds silence, not work
        unit = loopback_unit("u0", retry_interval=0.01, max_retries=5)
        recs = self._drive(
            unit, [Chunk(0, 1, "u0")],
            lambda c: time.sleep(0.4) or 41 + c.size,
        )
        assert recs[0].error is None
        assert recs[0].result == 42

    def test_handshake_timeout_when_nobody_serves(self):
        client_end, _worker_end = LoopbackTransport.pair()  # no worker
        unit = RemoteUnit("u0", transport=client_end,
                          retry_interval=0.01, connect_timeout=0.2)
        with pytest.raises(TransportError, match="did not answer hello"):
            unit.start(CompletionBus())

    def test_elastic_leave_drains_remote_unit_gracefully(self):
        rec = Recorder(per_item_sleep=1e-4)
        rt = HeteroRuntime()
        rt.register_unit("r0", WorkerKind.CC, work_fn=rec,
                         backend=loopback_unit("r0"))
        rt.register_unit("cc0", WorkerKind.CC, work_fn=rec)
        rep = rt.parallel_for(
            num_items=150, policy="multidynamic", engine="interrupt",
            acc_chunk=8, elastic=ElasticSchedule().leave(0.004, "r0"),
        )
        assert rep.items == 150
        assert_exact_tiling(rep.coverage, 150)
        rec.assert_exactly_once(150)
        assert [e["action"] for e in rep.events] == ["leave"]
        # the drained unit stopped early; the survivor finished the space
        assert rep.per_worker_items["cc0"] > 0


# ---------------------------------------------------------------------------
# in-process TCP: late attach + sharded pinning validation
# ---------------------------------------------------------------------------
_TCP_LEDGER = Counter()
_TCP_LOCK = threading.Lock()


def _tcp_record(chunk):
    """Module-level so TCP pickling resolves it; in-process workers share
    this module's globals, so the ledger still observes side effects."""
    time.sleep(chunk.size * 5e-5)
    with _TCP_LOCK:
        _TCP_LEDGER.update(chunk.indices())


class TestTcpInProcess:
    def setup_method(self):
        with _TCP_LOCK:
            _TCP_LEDGER.clear()

    def test_remote_spec_through_register_unit(self):
        server = WorkerServer().start()
        try:
            rt = HeteroRuntime()
            rt.register_unit("r0", WorkerKind.CC, work_fn=_tcp_record,
                             backend=f"remote:{server.address}")
            rep = rt.parallel_for(num_items=120, engine="interrupt",
                                  acc_chunk=16)
            assert rep.items == 120
            assert_exact_tiling(rep.coverage, 120)
            with _TCP_LOCK:
                assert set(_TCP_LEDGER) == set(range(120))
                assert all(c == 1 for c in _TCP_LEDGER.values())
            assert set(rep.wire_latency) == {"r0"}
        finally:
            server.stop()

    def test_elastic_join_attaches_late_worker(self):
        # the worker is listening but no unit is attached until the join
        # event fires mid-run — "join = late worker attach"
        server = WorkerServer().start()
        try:
            rt = HeteroRuntime()
            rt.register_unit("cc0", WorkerKind.CC, work_fn=_tcp_record)
            rep = rt.parallel_for(
                _tcp_record, num_items=200, policy="multidynamic",
                engine="interrupt", acc_chunk=8,
                backend=f"remote:{server.address}",
                elastic=ElasticSchedule().join(0.002, "late", kind="cc"),
            )
            assert rep.items == 200
            assert_exact_tiling(rep.coverage, 200)
            with _TCP_LOCK:
                assert set(_TCP_LEDGER) == set(range(200))
                assert all(c == 1 for c in _TCP_LEDGER.values())
            assert rep.per_worker_items["late"] > 0
            assert [e["action"] for e in rep.events] == ["join"]
        finally:
            server.stop()

    def test_sharded_space_requires_pinning_remote_units(self):
        rt = HeteroRuntime()
        rt.register_unit("r0", WorkerKind.CC, work_fn=_tcp_record,
                         backend="remote:127.0.0.1:9")
        rt.register_unit("cc0", WorkerKind.CC, work_fn=_tcp_record)
        with pytest.raises(ValueError, match="pinned via placement"):
            rt.parallel_for(space=ShardedSpace(100, 2),
                            engine="interrupt")

    def test_sharded_space_rejects_call_level_remote_backend(self):
        rt = HeteroRuntime()
        rt.register_unit("cc0", WorkerKind.CC, work_fn=_tcp_record)
        rt.register_unit("cc1", WorkerKind.CC, work_fn=_tcp_record)
        with pytest.raises(ValueError, match="register per-unit remote"):
            rt.parallel_for(space=ShardedSpace(100, 2),
                            engine="interrupt",
                            backend="remote:127.0.0.1:9")


# ---------------------------------------------------------------------------
# make_backend: the remote spec form
# ---------------------------------------------------------------------------
class TestRemoteSpec:
    def test_remote_spec_builds_named_remote_unit(self):
        unit = make_backend("remote:127.0.0.1:12345", "acc0")
        assert isinstance(unit, RemoteUnit)
        assert unit.name == "acc0"
        assert unit.address == "127.0.0.1:12345"

    def test_remote_spec_without_address_rejected(self):
        with pytest.raises(ValueError, match="remote:<host:port>"):
            make_backend("remote:", "u0")

    def test_register_unit_accepts_remote_spec(self):
        rt = HeteroRuntime()
        spec = rt.register_unit("r0", WorkerKind.ACC, work_fn=lambda c: None,
                                backend="remote:127.0.0.1:12345")
        assert spec.backend == "remote:127.0.0.1:12345"

    def test_no_proxy_chains(self):
        with pytest.raises(ValueError, match="no proxy chains"):
            RemoteUnit("u0", address="127.0.0.1:1",
                       remote_backend="remote:127.0.0.1:2")


# ---------------------------------------------------------------------------
# worker loss: the medium dies, the run does not
# ---------------------------------------------------------------------------
class DropDoneTransport(FlakyTransport):
    """Drops every ``done``/``done_batch``/``busy`` frame: the
    worker→client channel is dead while submits still flow — retransmit
    exhaustion, deterministic."""

    def __init__(self, inner):
        super().__init__(inner, seed=0)

    def send(self, frame):
        if isinstance(frame, dict) and frame.get("kind") in (
                "done", "done_batch", "busy"):
            return
        self.inner.send(frame)


class TestWorkerLost:
    def test_connection_drop_requeues_inflight_to_survivors(self):
        # the work function itself severs the worker's transport after a
        # few chunks: the executed-but-unreported chunk must be requeued
        # (coverage exact-once) even though its side effects already
        # landed — the documented at-least-once boundary of worker loss
        client_end, worker_end = LoopbackTransport.pair()
        worker = RemoteWorker(worker_end, poll_interval=0.05)
        threading.Thread(target=worker.serve, daemon=True).start()

        seen, lock = set(), threading.Lock()
        state = {"executions": 0}

        def work(chunk):
            with lock:
                seen.update(chunk.indices())
                state["executions"] += 1
                if state["executions"] == 3:
                    worker_end.close()  # completion of this chunk is unsendable
            time.sleep(chunk.size * 1e-4)

        rt = HeteroRuntime()
        rt.register_unit("r0", WorkerKind.CC, work_fn=work,
                         backend=RemoteUnit("r0", transport=client_end,
                                            retry_interval=0.02,
                                            max_retries=25))
        rt.register_unit("cc0", WorkerKind.CC, work_fn=work)
        rt.register_unit("cc1", WorkerKind.CC, work_fn=work)
        rep = rt.parallel_for(num_items=240, policy="multidynamic",
                              engine="interrupt", acc_chunk=8)
        assert rep.items == 240
        assert_exact_tiling(rep.coverage, 240)
        assert set(range(240)) <= seen
        lost = [e for e in rep.events if e["action"] == "lost"]
        assert len(lost) == 1 and lost[0]["unit"] == "r0"
        assert lost[0]["requeued"] is not None

    def test_retransmit_exhaustion_is_a_lost_worker_not_a_hang(self):
        # completions never arrive (all done frames dropped): after
        # max_retries the unit posts WorkerLost and the survivor finishes
        client_end, worker_end = LoopbackTransport.pair()
        worker = RemoteWorker(DropDoneTransport(worker_end),
                              poll_interval=0.02)
        threading.Thread(target=worker.serve, daemon=True).start()

        rec = Recorder(per_item_sleep=1e-5)
        rt = HeteroRuntime()
        rt.register_unit("r0", WorkerKind.CC, work_fn=rec,
                         backend=RemoteUnit("r0", transport=client_end,
                                            retry_interval=0.01,
                                            max_retries=5))
        rt.register_unit("cc0", WorkerKind.CC, work_fn=rec)
        rep = rt.parallel_for(num_items=100, policy="multidynamic",
                              engine="interrupt", acc_chunk=8)
        assert rep.items == 100
        assert_exact_tiling(rep.coverage, 100)
        lost = [e for e in rep.events if e["action"] == "lost"]
        assert len(lost) == 1 and lost[0]["unit"] == "r0"
        # every index ran at least once; only the requeued span may repeat
        assert set(rec.counts) == set(range(100))

    def test_all_workers_lost_raises_stall_not_hang(self):
        client_end, worker_end = LoopbackTransport.pair()
        worker = RemoteWorker(DropDoneTransport(worker_end),
                              poll_interval=0.02)
        threading.Thread(target=worker.serve, daemon=True).start()
        rt = HeteroRuntime()
        rt.register_unit("r0", WorkerKind.CC, work_fn=lambda c: None,
                         backend=RemoteUnit("r0", transport=client_end,
                                            retry_interval=0.01,
                                            max_retries=5))
        with pytest.raises(RuntimeError, match="stalled"):
            rt.parallel_for(num_items=50, engine="interrupt", acc_chunk=8)


# ---------------------------------------------------------------------------
# the FlakyTransport battery (the ISSUE's headline)
# ---------------------------------------------------------------------------
def flaky_battery_run(seed):
    """One randomized multi-host run over faulty loopback transports."""
    import random

    rng = random.Random(seed)
    n_remote = rng.randint(2, 3)
    n_local = rng.randint(0, 1)
    n_items = rng.randint(80, 240)
    acc_chunk = rng.choice([4, 8, 16])
    policy = POLICIES[rng.randrange(3)]
    faults = dict(
        drop=rng.uniform(0.0, 0.25),
        duplicate=rng.uniform(0.0, 0.25),
        reorder=rng.uniform(0.0, 0.25),
        delay=rng.uniform(0.0, 0.3),
        max_delay=0.01,
    )
    # dispatch fast-path knobs ride the same battery: descriptor caching
    # and frame batching must preserve exact-once under every fault mix
    # (drop/dup/reorder now also hit register_fn / work_batch /
    # done_batch frames)
    batch_frames = rng.choice([1, 1, 2, 4])
    fn_cache = rng.random() < 0.75
    rec = Recorder(per_item_sleep=rng.uniform(0.5, 2.0) * 2e-5)
    rt = HeteroRuntime()
    for i in range(n_remote):
        rt.register_unit(
            f"r{i}", WorkerKind.CC, work_fn=rec,
            backend=loopback_unit(f"r{i}", flaky_seed=seed * 37 + i * 1000,
                                  batch_frames=batch_frames,
                                  fn_cache=fn_cache, **faults),
        )
    for i in range(n_local):
        rt.register_unit(f"cc{i}", WorkerKind.CC, work_fn=rec)

    elastic = None
    if n_remote + n_local >= 3 and rng.random() < 0.5:
        # drain one remote unit mid-run; survivors must still cover
        elastic = ElasticSchedule().leave(
            rng.uniform(0.0, 0.05), f"r{rng.randrange(n_remote)}")

    rep = rt.parallel_for(
        num_items=n_items, policy=policy, engine="interrupt",
        acc_chunk=acc_chunk, elastic=elastic,
    )
    return rep, rec, n_items


class TestFlakyBattery:
    """≥20 seeded drop/delay/duplicate/reorder schedules: exact-once."""

    @given(seed=st.integers(0, 10**6))
    @settings(max_examples=20, deadline=None)
    def test_exact_once_under_faulty_medium(self, seed):
        rep, rec, n_items = flaky_battery_run(seed)
        assert rep.items == n_items
        assert rep.chunks == len(rep.coverage)
        assert_exact_tiling(rep.coverage, n_items)
        rec.assert_exactly_once(n_items)
        times = [e["t"] for e in (rep.events or [])]
        assert times == sorted(times), "events not monotone"

    def test_side_effect_parity_with_inline(self):
        # the same workload through a faulty transport and through the
        # inline backend must leave identical ledgers behind
        n_items = 180

        def run_remote():
            rec = Recorder(per_item_sleep=1e-5)
            rt = HeteroRuntime()
            for i in range(2):
                rt.register_unit(
                    f"r{i}", WorkerKind.CC, work_fn=rec,
                    backend=loopback_unit(f"r{i}", flaky_seed=1234 + i,
                                          drop=0.2, duplicate=0.2,
                                          reorder=0.2, delay=0.2,
                                          max_delay=0.01),
                )
            rep = rt.parallel_for(num_items=n_items, policy="static",
                                  engine="interrupt", acc_chunk=8)
            return rep, rec

        def run_inline():
            rec = Recorder()
            rt = HeteroRuntime()
            for i in range(2):
                rt.register_unit(f"r{i}", WorkerKind.CC, work_fn=rec,
                                 backend="inline")
            rep = rt.parallel_for(num_items=n_items, policy="static",
                                  engine="interrupt", acc_chunk=8)
            return rep, rec

        rep_r, rec_r = run_remote()
        rep_i, rec_i = run_inline()
        assert rec_r.counts == rec_i.counts, "side effects diverged"
        assert rep_r.items == rep_i.items == n_items
        assert_exact_tiling(rep_r.coverage, n_items)
        assert_exact_tiling(rep_i.coverage, n_items)


# ---------------------------------------------------------------------------
# dispatch fast path (ISSUE 8): session-cached work descriptors and
# chunk-batched frames — wire-shape, recovery, and accounting contracts
# ---------------------------------------------------------------------------
class _DropFirstRegistration(FrameTap):
    """Swallows the first register_fn frame (still recorded in .sent)."""

    def _forward(self, frame):
        if frame.get("kind") == "register_fn" and not getattr(
                self, "_dropped", False):
            self._dropped = True
            return
        self.inner.send(frame)


def _drive_direct(unit, chunks, work_fn):
    """Submit every chunk up-front (pipelined), wait for all completions."""
    bus = CompletionBus()
    unit.start(bus)
    try:
        for c in chunks:
            unit.submit(c, work_fn)
        unit.flush()
        recs = []
        deadline = time.perf_counter() + 30.0
        while len(recs) < len(chunks):
            assert time.perf_counter() < deadline, (
                f"only {len(recs)}/{len(chunks)} completions arrived")
            bus.wait(timeout=1.0)
            recs.extend(bus.drain())
        return recs
    finally:
        unit.close()


def _work_items(tap):
    """All work items the client ever put on the wire, batched or not."""
    items = []
    for f in tap.frames("submit"):
        items.append(f)
    for f in tap.frames("work_batch"):
        items.extend(f["items"])
    return items


class TestDescriptorCache:
    def test_fn_registered_once_per_session(self):
        unit, tap, _w = tapped_loopback_unit("u0")
        fn = SleepWork(0.0)
        recs = _drive_direct(
            unit, [Chunk(i, i + 1, "u0") for i in range(6)], fn)
        assert len(recs) == 6 and all(r.error is None for r in recs)
        assert len(tap.frames("register_fn")) == 1
        items = _work_items(tap)
        assert len(items) >= 6
        assert all("fn" not in it and "fn_ref" in it for it in items), (
            "work items must reference the cached descriptor, not inline it")

    def test_content_hash_shares_and_invalidates_registrations(self):
        unit, tap, _w = tapped_loopback_unit("u0")
        bus = CompletionBus()
        unit.start(bus)
        try:
            def one(chunk, fn):
                unit.submit(chunk, fn)
                unit.flush()
                while not bus.drain():
                    bus.wait(timeout=5.0)

            # two *distinct objects* with equal pickled content: one reg
            one(Chunk(0, 1, "u0"), SleepWork(0.0))
            one(Chunk(1, 2, "u0"), SleepWork(0.0))
            assert len(tap.frames("register_fn")) == 1
            # changed content hashes differently: re-registers
            one(Chunk(2, 3, "u0"), SleepWork(1e-9))
            regs = tap.frames("register_fn")
            assert len(regs) == 2
            assert regs[0]["fn_id"] != regs[1]["fn_id"]
            assert all(r["fn_id"].startswith("h:") for r in regs)
        finally:
            unit.close()

    def test_unpicklable_fn_falls_back_to_identity_id(self):
        # loopback lambdas/closures cannot be content-hashed; they get a
        # session-stable identity id and still ride the cache path
        unit, tap, _w = tapped_loopback_unit("u0")
        hits = []
        fn = lambda c: hits.append(c.start)  # noqa: E731
        recs = _drive_direct(
            unit, [Chunk(i, i + 1, "u0") for i in range(3)], fn)
        assert len(recs) == 3 and sorted(hits) == [0, 1, 2]
        regs = tap.frames("register_fn")
        assert len(regs) == 1 and regs[0]["fn_id"].startswith("r:")

    def test_fn_cache_off_inlines_the_fn(self):
        unit, tap, _w = tapped_loopback_unit("u0", fn_cache=False)
        recs = _drive_direct(
            unit, [Chunk(i, i + 1, "u0") for i in range(4)], SleepWork(0.0))
        assert len(recs) == 4
        assert not tap.frames("register_fn")
        items = _work_items(tap)
        assert all("fn" in it and "fn_ref" not in it for it in items)

    def test_dropped_registration_before_batched_work_recovers(self):
        # the ISSUE's directed case: register_fn lost, then a work_batch
        # arrives referencing it — the worker NACKs unknown_fn, the
        # client re-registers and retransmits, exact-once is preserved
        unit, tap, _w = tapped_loopback_unit(
            "u0", batch_frames=4, tap_cls=_DropFirstRegistration)
        rec = Recorder()
        chunks = [Chunk(i * 2, i * 2 + 2, "u0") for i in range(4)]
        recs = _drive_direct(unit, chunks, rec)
        assert len(recs) == 4 and all(r.error is None for r in recs)
        rec.assert_exactly_once(8)
        assert len(tap.frames("register_fn")) >= 2, (
            "client never re-registered after the unknown_fn NACK")
        assert tap.frames("work_batch"), "batching was not engaged"

    def test_worker_registry_loss_mid_session_recovers(self):
        # a worker that lost its per-session fn registry (restart) NACKs
        # the next cached reference; the client re-ships the descriptor
        unit, tap, worker = tapped_loopback_unit("u0")
        bus = CompletionBus()
        unit.start(bus)
        try:
            fn = SleepWork(0.0)
            unit.submit(Chunk(0, 2, "u0"), fn)
            unit.flush()
            while not bus.drain():
                bus.wait(timeout=5.0)
            with worker._lock:
                worker._fns.clear()  # simulate restart-shaped amnesia
            unit.submit(Chunk(2, 4, "u0"), fn)
            unit.flush()
            recs = []
            deadline = time.perf_counter() + 10.0
            while not recs:
                assert time.perf_counter() < deadline
                bus.wait(timeout=1.0)
                recs = bus.drain()
            assert recs[0].error is None
            assert len(tap.frames("register_fn")) == 2
        finally:
            unit.close()


class TestBatchedFrames:
    def test_full_batch_coalesces_into_one_work_batch(self):
        unit, tap, _w = tapped_loopback_unit("u0", batch_frames=4)
        rec = Recorder()
        chunks = [Chunk(i * 3, i * 3 + 3, "u0") for i in range(4)]
        recs = _drive_direct(unit, chunks, rec)
        assert len(recs) == 4
        rec.assert_exactly_once(12)
        batches = tap.frames("work_batch")
        assert len(batches) == 1 and len(batches[0]["items"]) == 4
        assert not tap.frames("submit"), (
            "chunks leaked out as singleton frames despite batching")

    def test_partial_batch_stays_buffered_until_flush(self):
        unit, tap, _w = tapped_loopback_unit("u0", batch_frames=8)
        bus = CompletionBus()
        unit.start(bus)
        try:
            for i in range(3):
                unit.submit(Chunk(i, i + 1, "u0"), SleepWork(0.0))
            assert not _work_items(tap), (
                "a partial batch went on the wire before flush()")
            unit.flush()
            recs = []
            deadline = time.perf_counter() + 10.0
            while len(recs) < 3:
                assert time.perf_counter() < deadline
                bus.wait(timeout=1.0)
                recs.extend(bus.drain())
            batches = tap.frames("work_batch")
            assert len(batches) == 1 and len(batches[0]["items"]) == 3
        finally:
            unit.close()

    def test_batch_frames_1_keeps_legacy_frame_shapes(self):
        # parity satellite: a batch_frames=1, fn_cache=off session must
        # put exactly the pre-fast-path frames on the wire...
        unit, tap, _w = tapped_loopback_unit("u0", fn_cache=False)
        rec_legacy = Recorder()
        chunks = [Chunk(i * 4, i * 4 + 4, "u0") for i in range(5)]
        _drive_direct(unit, chunks, rec_legacy)
        kinds = set(tap.kinds())
        assert kinds <= {"hello", "submit", "bye"}, f"new kinds leaked: {kinds}"
        for f in tap.frames("submit"):
            assert {"kind", "unit", "seq", "chunk", "fn",
                    "t_submit", "floor"} <= set(f)
        # ...and produce results identical to the batched+cached path
        unit2, _tap2, _w2 = tapped_loopback_unit(
            "u0", batch_frames=4, fn_cache=True)
        rec_fast = Recorder()
        _drive_direct(unit2, chunks, rec_fast)
        assert rec_fast.counts == rec_legacy.counts

    def test_batched_cached_exact_once_under_faults(self):
        # directed heavy-fault run with the fast path fully on: drops,
        # dups and reorders now hit register_fn/work_batch/done_batch
        rec = Recorder(per_item_sleep=1e-5)
        rt = HeteroRuntime()
        for i in range(2):
            rt.register_unit(
                f"r{i}", WorkerKind.CC, work_fn=rec,
                backend=loopback_unit(f"r{i}", flaky_seed=4242 + i,
                                      batch_frames=4, fn_cache=True,
                                      drop=0.25, duplicate=0.25,
                                      reorder=0.25, delay=0.2,
                                      max_delay=0.01),
            )
        rep = rt.parallel_for(num_items=160, policy="multidynamic",
                              engine="interrupt", acc_chunk=8)
        assert rep.items == 160
        assert_exact_tiling(rep.coverage, 160)
        rec.assert_exactly_once(160)
        assert rep.wire_latency is not None
        times = [e["t"] for e in (rep.events or [])]
        assert times == sorted(times)


class _NullSink:
    """Transport stub for white-box accounting tests: swallows sends."""

    closed = False

    def send(self, frame):
        pass

    def recv(self, timeout=None):
        return None

    def close(self):
        pass


class TestWireAccounting:
    """RunReport.wire_latency on synthetic done-frames: a batched frame's
    transit is attributed per chunk — counted once per frame, not once
    per chunk — with no clocks involved (fixed synthetic timestamps)."""

    @staticmethod
    def _unit_with_pending(batch_n, t_submit, t_sent):
        from repro.core.backends import BackendUnit

        unit = RemoteUnit("u0", transport=_NullSink(), batch_frames=batch_n)
        bus = CompletionBus()
        BackendUnit.start(unit, bus)  # skip handshake: frames are synthetic
        for seq in range(batch_n):
            unit._pending[seq] = {
                "seq": seq, "chunk": Chunk(seq * 4, seq * 4 + 4, "u0"),
                "fn": SleepWork(0.0), "t_submit": t_submit,
                "t_sent": t_sent, "sends": 1,
                "next_resend": float("inf"), "batch_n": batch_n,
            }
        return unit, bus

    def test_batched_transit_counted_once_across_the_frame(self):
        unit, bus = self._unit_with_pending(3, t_submit=100.0, t_sent=100.5)
        transit = 0.4  # t_accept - t_sent, shared by all 3 chunks
        queue_waits = [0.0, 0.1, 0.2]  # t_start - t_accept, per chunk
        unit._on_frame({"kind": "done_batch", "unit": "u0", "items": [
            {"seq": s, "chunk": Chunk(s * 4, s * 4 + 4, "u0"),
             "elapsed": 0.01, "t_accept": 100.5 + transit,
             "t_start": 100.5 + transit + queue_waits[s],
             "error": None, "result": None}
            for s in range(3)]})
        assert len(unit.wire_latencies) == 3
        # each chunk: 1/3 of the frame transit + its own queue wait
        for wire, qw in zip(unit.wire_latencies, queue_waits):
            assert wire == pytest.approx(transit / 3 + qw)
        # summed over the batch the transit appears exactly once
        assert sum(unit.wire_latencies) == pytest.approx(
            transit + sum(queue_waits))
        recs = bus.drain()
        assert [r.dispatch_latency for r in recs] == pytest.approx(
            [100.5 + transit + qw - 100.0 for qw in queue_waits])
        assert unit.local_queue_latencies == pytest.approx([0.5] * 3)

    def test_singleton_reduces_to_legacy_attribution(self):
        # batch_n == 1: wire == t_start - t_sent, exactly the pre-batching
        # definition (transit/1 + queue wait telescopes)
        unit, bus = self._unit_with_pending(1, t_submit=50.0, t_sent=50.2)
        unit._on_frame({"kind": "done", "unit": "u0", "seq": 0,
                        "chunk": Chunk(0, 4, "u0"), "elapsed": 0.01,
                        "t_accept": 50.6, "t_start": 50.9,
                        "error": None, "result": None})
        assert unit.wire_latencies == pytest.approx([50.9 - 50.2])
        assert len(bus.drain()) == 1

    def test_duplicate_done_items_do_not_double_count(self):
        unit, bus = self._unit_with_pending(2, t_submit=10.0, t_sent=10.1)
        frame = {"kind": "done_batch", "unit": "u0", "items": [
            {"seq": s, "chunk": Chunk(s * 4, s * 4 + 4, "u0"),
             "elapsed": 0.01, "t_accept": 10.3, "t_start": 10.3,
             "error": None, "result": None} for s in range(2)]}
        unit._on_frame(frame)
        unit._on_frame(frame)  # duplicated done_batch (flaky medium)
        assert len(unit.wire_latencies) == 2
        assert len(bus.drain()) == 2


class TestRemoteSpecKnobs:
    def test_spec_query_string_sets_fast_path_knobs(self):
        unit = make_backend("remote:127.0.0.1:9?batch_frames=4&fn_cache=0",
                            "r0")
        assert isinstance(unit, RemoteUnit)
        assert unit.batch_frames == 4 and unit.capacity == 4
        assert unit.fn_cache is False

    def test_spec_defaults_are_conservative(self):
        unit = make_backend("remote:127.0.0.1:9", "r0")
        assert unit.batch_frames == 1 and unit.capacity == 1
        assert unit.fn_cache is True

    def test_unknown_knob_rejected_with_listing(self):
        with pytest.raises(ValueError, match="batch_frames"):
            make_backend("remote:127.0.0.1:9?turbo=1", "r0")

    def test_non_integer_knob_value_rejected(self):
        with pytest.raises(ValueError):
            make_backend("remote:127.0.0.1:9?batch_frames=lots", "r0")

    def test_batch_frames_must_be_positive(self):
        with pytest.raises(ValueError, match="batch_frames"):
            make_backend("remote:127.0.0.1:9?batch_frames=0", "r0")

    def test_batch_frames_auto_spec(self):
        unit = make_backend("remote:127.0.0.1:9?batch_frames=auto", "r0")
        assert isinstance(unit, RemoteUnit)
        assert unit.auto_batch is True
        # adaptation starts narrow and only widens from latency evidence
        assert unit.batch_frames == 1 and unit.capacity == 1

    def test_auto_is_only_for_batch_frames(self):
        with pytest.raises(ValueError):
            make_backend("remote:127.0.0.1:9?fn_cache=auto", "r0")


# ---------------------------------------------------------------------------
# adaptive frame batching (ISSUE 9 tentpole): batch_frames="auto"
# ---------------------------------------------------------------------------
class TestAdaptiveFrameBatching:
    def _drive(self, unit, n_chunks, work_fn):
        """Pump chunks through the unit, windowed at its (live) capacity."""
        bus = CompletionBus()
        unit.start(bus)
        try:
            issued = done = 0
            while done < n_chunks:
                while issued < n_chunks and issued - done < unit.capacity:
                    unit.submit(Chunk(issued, issued + 1, unit.name), work_fn)
                    issued += 1
                unit.flush()
                assert bus.wait(timeout=30.0), (
                    f"completions stalled at {done}/{n_chunks}")
                for rec in bus.drain():
                    assert rec.error is None
                    done += 1
        finally:
            unit.close()

    def test_constructor_rejects_bad_string(self):
        client_end, _ = LoopbackTransport.pair()
        with pytest.raises(ValueError, match="batch_frames"):
            RemoteUnit("u0", transport=client_end, batch_frames="lots")

    def test_auto_widens_on_delayed_link(self):
        # every frame in both directions pays uniform(0, 8 ms): frame
        # transit dwarfs the near-zero service time, so the learned width
        # must open up from 1 — and exact-once execution must survive the
        # batching transitions
        client_end, worker_end = LoopbackTransport.pair()
        client_side = FlakyTransport(client_end, seed=11,
                                     delay=1.0, max_delay=0.008)
        worker_side = FlakyTransport(worker_end, seed=12,
                                     delay=1.0, max_delay=0.008)
        worker = RemoteWorker(worker_side, poll_interval=0.02)
        threading.Thread(target=worker.serve, daemon=True).start()
        unit = RemoteUnit("u0", transport=client_side, retry_interval=0.5,
                          max_retries=200, batch_frames="auto")
        rec = Recorder()
        self._drive(unit, 160, rec)
        rec.assert_exactly_once(160)
        assert unit.auto_batch
        assert 1 < unit.effective_batch_frames <= AUTO_BATCH_MAX
        # capacity tracks the live width so drivers can keep the pipe full
        assert unit.capacity == unit.effective_batch_frames

    def test_auto_stays_narrow_on_clean_link(self):
        # loopback transit is microseconds while each chunk takes ~2 ms of
        # service: batching would add latency for nothing, width stays 1
        unit = loopback_unit("u0", batch_frames="auto")
        rec = Recorder(per_item_sleep=2e-3)
        self._drive(unit, 30, rec)
        rec.assert_exactly_once(30)
        assert unit.effective_batch_frames == 1

    def test_runreport_carries_effective_width(self):
        rec = Recorder(per_item_sleep=2e-5)
        rt = HeteroRuntime()
        rt.register_unit("r0", WorkerKind.CC, work_fn=rec,
                         backend=loopback_unit("r0", batch_frames="auto"))
        rt.register_unit("cc0", WorkerKind.CC, work_fn=rec)
        rep = rt.parallel_for(num_items=200, policy="multidynamic",
                              engine="interrupt", acc_chunk=16)
        assert_exact_tiling(rep.coverage, 200)
        rec.assert_exactly_once(200)
        # only transport units report a frame width; local units have none
        assert rep.batch_frames is not None
        assert set(rep.batch_frames) == {"r0"}
        assert 1 <= rep.batch_frames["r0"] <= AUTO_BATCH_MAX

    def test_lost_pipelined_unit_requeues_all_outstanding(self):
        # capacity 3 (batch_frames=3): the unit dies holding three chunks.
        # Regression: abort used to surrender only the oldest in-flight
        # chunk, so two spans vanished and the run hung or under-covered;
        # now every dropped span requeues and the survivor finishes the
        # space exact-once.
        client_end, worker_end = LoopbackTransport.pair()
        worker = RemoteWorker(DropDoneTransport(worker_end),
                              poll_interval=0.02)
        threading.Thread(target=worker.serve, daemon=True).start()
        rec = Recorder(per_item_sleep=1e-5)
        rt = HeteroRuntime()
        rt.register_unit("r0", WorkerKind.CC, work_fn=rec,
                         backend=RemoteUnit("r0", transport=client_end,
                                            retry_interval=0.01,
                                            max_retries=5, batch_frames=3))
        rt.register_unit("cc0", WorkerKind.CC, work_fn=rec)
        rep = rt.parallel_for(num_items=120, policy="multidynamic",
                              engine="interrupt", acc_chunk=8)
        assert rep.items == 120
        assert_exact_tiling(rep.coverage, 120)
        lost = [e for e in rep.events if e["action"] == "lost"]
        assert len(lost) == 1 and lost[0]["unit"] == "r0"
        # every index ran at least once; requeued spans may legitimately
        # repeat (the worker executed them but the done frames were lost)
        assert set(rec.counts) == set(range(120))


# ---------------------------------------------------------------------------
# worker subprocesses over real TCP (the acceptance criterion)
# ---------------------------------------------------------------------------
def _index_bytes(i: int) -> bytes:
    return ((i * 2654435761) % 2**32).to_bytes(4, "big") * 4


class ChunkWriter:
    """Picklable work: one file per index (idempotent) + an append log."""

    def __init__(self, root):
        self.root = root

    def __call__(self, chunk):
        for i in chunk.indices():
            with open(os.path.join(self.root, f"{i:06d}.bin"), "wb") as f:
                f.write(_index_bytes(i))
        with open(os.path.join(self.root, "log.txt"), "a") as f:
            f.write(f"{chunk.start}:{chunk.stop}\n")


def _sleepy_noop(chunk):
    time.sleep(chunk.size * 2e-3)


def _result_platforms(chunk):
    """Module-level so a worker can unpickle it: where jnp computed."""
    import jax.numpy as jnp

    return sorted(d.platform for d in jnp.arange(chunk.size).devices())


def read_results(root) -> bytes:
    names = sorted(n for n in os.listdir(root) if n.endswith(".bin"))
    return b"".join(
        open(os.path.join(root, n), "rb").read() for n in names
    ), names


def read_log_spans(root):
    with open(os.path.join(root, "log.txt")) as f:
        return sorted(tuple(map(int, line.split(":"))) for line in f)


@pytest.fixture(scope="module")
def worker_pair():
    workers = [spawn_worker(), spawn_worker()]
    yield workers
    for w in workers:
        w.terminate()


class TestSubprocessWorkers:
    def test_sharded_remote_parity_with_inline(self, worker_pair, tmp_path):
        # THE acceptance line: one parallel_for over a ShardedSpace with
        # two RemoteUnits on SocketTransport worker subprocesses ==
        # byte-identical results + exact-once coverage vs backend="inline"
        n_items = 160
        w0, w1 = worker_pair

        def run(backend_for, root):
            os.makedirs(root, exist_ok=True)
            work = ChunkWriter(str(root))
            rt = HeteroRuntime()
            rt.register_unit("r0", WorkerKind.CC, work_fn=work,
                             backend=backend_for("r0", w0))
            rt.register_unit("r1", WorkerKind.CC, work_fn=work,
                             backend=backend_for("r1", w1))
            sp = ShardedSpace(n_items, 2, placement={"r0": 0, "r1": 1})
            return rt.parallel_for(space=sp, policy="multidynamic",
                                   engine="interrupt", acc_chunk=8)

        rep_remote = run(lambda name, w: f"remote:{w.address}",
                         tmp_path / "remote")
        rep_inline = run(lambda name, w: "inline", tmp_path / "inline")

        for rep, root in ((rep_remote, tmp_path / "remote"),
                          (rep_inline, tmp_path / "inline")):
            assert rep.items == n_items
            assert_exact_tiling(rep.coverage, n_items)
            # exact-once side effects *in the executing process*: the log
            # spans tile the space with no duplicates
            assert_exact_tiling(read_log_spans(root), n_items)

        blob_remote, names_remote = read_results(tmp_path / "remote")
        blob_inline, names_inline = read_results(tmp_path / "inline")
        assert names_remote == names_inline
        assert blob_remote == blob_inline, "remote results diverged from inline"

        # the dispatch-latency split is populated for the remote run only
        assert set(rep_remote.wire_latency) == {"s0/r0", "s1/r1"}
        assert rep_inline.wire_latency is None

    def test_worker_jax_unit_is_a_cpu_unit_whatever_the_parent_says(
            self, monkeypatch):
        # the chip belongs to the parent: a spawned worker runs jax on the
        # CPU even when the parent's environment names an accelerator, and
        # its "jax" unit reports the platform it really computes on
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        handle = spawn_worker()
        unit = RemoteUnit("j0", address=handle.address, remote_backend="jax")
        bus = CompletionBus()
        try:
            unit.start(bus)
            assert unit.platform == "cpu"
            unit.submit(Chunk(0, 3, "j0"), _result_platforms)
            assert bus.wait(timeout=60.0)
            rec = bus.drain()[0]
            assert rec.error is None, rec.error
            assert rec.result == ["cpu"]
        finally:
            unit.close()
            handle.terminate()

    def test_killed_worker_subprocess_does_not_hang_the_run(self):
        handle = spawn_worker()
        try:
            rt = HeteroRuntime()
            rt.register_unit(
                "r0", WorkerKind.CC, work_fn=_sleepy_noop,
                backend=RemoteUnit("r0", address=handle.address,
                                   retry_interval=0.05, max_retries=20),
            )
            rt.register_unit("cc0", WorkerKind.CC, work_fn=_sleepy_noop)
            rt.register_unit("cc1", WorkerKind.CC, work_fn=_sleepy_noop)
            killer = threading.Timer(0.15, handle.kill)
            killer.start()
            try:
                rep = rt.parallel_for(num_items=300, policy="multidynamic",
                                      engine="interrupt", acc_chunk=8)
            finally:
                killer.cancel()
            assert rep.items == 300
            assert_exact_tiling(rep.coverage, 300)
            lost = [e for e in (rep.events or []) if e["action"] == "lost"]
            assert len(lost) <= 1  # at most one loss event for one worker
        finally:
            handle.terminate()


# ---------------------------------------------------------------------------
# heartbeat liveness (ISSUE 10): silence is detected, slowness is not
# ---------------------------------------------------------------------------
def heartbeat_loopback_unit(name, *, heartbeat=0.02, patience=3,
                            retry_interval=0.02, max_retries=600,
                            hb_seed=None, **hb_faults):
    """A loopback unit with heartbeat liveness; ``hb_faults`` (with
    ``hb_seed``) fault ONLY the worker's heartbeat frames — work and
    completion frames ride a clean medium."""
    client_end, worker_end = LoopbackTransport.pair()
    worker_side = worker_end
    if hb_seed is not None:
        worker_side = FlakyTransport(worker_end, seed=hb_seed,
                                     kinds=("heartbeat",), **hb_faults)
    worker = RemoteWorker(worker_side, poll_interval=0.02)
    threading.Thread(target=worker.serve, daemon=True).start()
    unit = RemoteUnit(name, transport=client_end,
                      retry_interval=retry_interval, max_retries=max_retries,
                      heartbeat=heartbeat, patience=patience)
    return unit, worker


class _PartitionOnWork:
    """Worker-side medium that goes dark the instant the first work frame
    arrives: the frame is swallowed *before* delivery and everything
    after it (heartbeats included) is silently dropped — a frozen
    process / network partition, as opposed to a visible EOF."""

    def __init__(self, inner):
        self.inner = inner
        self.dark = threading.Event()

    def send(self, frame):
        if self.dark.is_set():
            return
        self.inner.send(frame)

    def recv(self, timeout=None):
        frame = self.inner.recv(timeout)
        if frame is not None and frame.get("kind") in ("submit",
                                                       "work_batch"):
            self.dark.set()
        if self.dark.is_set():
            return None
        return frame

    def close(self):
        self.inner.close()

    @property
    def closed(self):
        return self.inner.closed


class TestHeartbeatLiveness:
    def test_heartbeat_spec_knobs_parse(self):
        unit = make_backend("remote:127.0.0.1:1?heartbeat=0.5&patience=5",
                            "r0")
        assert unit.heartbeat == 0.5
        assert unit.patience == 5

    def test_heartbeat_defaults_off(self):
        unit = make_backend("remote:127.0.0.1:1", "r0")
        assert unit.heartbeat is None

    def test_heartbeat_knob_must_be_numeric(self):
        with pytest.raises(ValueError, match="number of seconds"):
            make_backend("remote:127.0.0.1:1?heartbeat=fast", "r0")

    def test_heartbeat_knob_must_be_positive(self):
        with pytest.raises(ValueError, match="positive"):
            make_backend("remote:127.0.0.1:1?heartbeat=0", "r0")

    def test_patience_must_be_at_least_one(self):
        with pytest.raises(ValueError, match="patience"):
            RemoteUnit("r0", address="127.0.0.1:1", heartbeat=0.1,
                       patience=0)

    def test_worker_sends_heartbeats_with_queue_depth(self):
        unit, _worker = heartbeat_loopback_unit("r0", heartbeat=0.02)
        rec = Recorder(per_item_sleep=1e-4)
        bus = CompletionBus()
        unit.start(bus)
        try:
            for i in range(4):
                unit.submit(Chunk(i * 10, (i + 1) * 10, "r0"), rec)
            unit.flush()
            recs = []
            deadline = time.perf_counter() + 10.0
            # wait for all completions AND at least one liveness frame
            while (len(recs) < 4 or unit.last_heartbeat is None):
                assert time.perf_counter() < deadline, (
                    f"{len(recs)}/4 done, beat={unit.last_heartbeat}")
                bus.wait(timeout=0.2)
                recs.extend(bus.drain())
        finally:
            unit.close()
        assert len(recs) == 4 and not any(r.error for r in recs)
        beat = unit.last_heartbeat
        assert beat["unit"] == "r0"
        assert beat["queue_depth"] >= 0 and beat["inflight"] >= 0
        rec.assert_exactly_once(40)

    def test_silent_partition_is_convicted_dead_not_hung(self):
        # the worker freezes before executing anything: heartbeats stop,
        # the connection never drops.  Without conviction the client
        # would burn max_retries * retry_interval = 30s; with it, the
        # run ends in ~patience * heartbeat and the survivor covers the
        # space with STRICT exact-once side effects (the frozen worker
        # never ran its chunk).
        client_end, worker_end = LoopbackTransport.pair()
        worker = RemoteWorker(_PartitionOnWork(worker_end),
                              poll_interval=0.02)
        threading.Thread(target=worker.serve, daemon=True).start()

        rec = Recorder(per_item_sleep=1e-5)
        rt = HeteroRuntime()
        rt.register_unit("r0", WorkerKind.CC, work_fn=rec,
                         backend=RemoteUnit("r0", transport=client_end,
                                            retry_interval=0.05,
                                            max_retries=600,
                                            heartbeat=0.02, patience=3))
        rt.register_unit("cc0", WorkerKind.CC, work_fn=rec)
        t0 = time.perf_counter()
        rep = rt.parallel_for(num_items=120, policy="multidynamic",
                              engine="interrupt", acc_chunk=8)
        wall = time.perf_counter() - t0
        assert rep.items == 120
        assert_exact_tiling(rep.coverage, 120)
        rec.assert_exactly_once(120)  # strict: the dead unit ran nothing
        dead = [e for e in rep.events if e["action"] == "dead"]
        assert len(dead) == 1 and dead[0]["unit"] == "r0"
        assert wall < 10.0, (
            f"conviction took {wall:.1f}s — heartbeat liveness did not "
            "beat the retransmit budget"
        )

    def test_idle_conviction_posts_membership_event_without_chunk(self):
        # silence with nothing in flight: the conviction is a pure
        # membership event (chunk=None), not a requeue
        client_end, worker_end = LoopbackTransport.pair()
        worker = RemoteWorker(worker_end, poll_interval=0.02)
        threading.Thread(target=worker.serve, daemon=True).start()
        mute = _PartitionOnWork(client_end)
        unit = RemoteUnit("r0", transport=mute, retry_interval=0.05,
                          max_retries=600, heartbeat=0.02, patience=3)
        bus = CompletionBus()
        unit.start(bus)
        try:
            # freeze the medium with nothing submitted
            mute.dark.set()
            deadline = time.perf_counter() + 10.0
            recs = []
            while not recs and time.perf_counter() < deadline:
                bus.wait(timeout=0.2)
                recs = bus.drain()
            assert recs, "idle conviction never posted"
            from repro.core import WorkerDead
            assert isinstance(recs[0].error, WorkerDead)
            assert recs[0].chunk is None
        finally:
            unit.close()

    def test_slow_worker_is_not_convicted(self):
        # per-item work far slower than the heartbeat interval: the
        # heartbeats keep flowing, so patience never runs out — slowness
        # is the straggler layer's problem, not a liveness verdict
        unit, _worker = heartbeat_loopback_unit("r0", heartbeat=0.02,
                                                patience=3)
        rec = Recorder(per_item_sleep=2e-3)  # 20ms/chunk >> heartbeat
        recs = _drive_direct(unit, [Chunk(i * 10, (i + 1) * 10, "r0")
                                    for i in range(6)], rec)
        assert len(recs) == 6
        assert not any(r.error for r in recs)
        rec.assert_exactly_once(60)


def heartbeat_battery_run(seed):
    """One seeded run with faults injected ONLY into heartbeat frames
    (drop/delay), while a slow-but-alive remote unit works: no false
    conviction is allowed."""
    import random

    rng = random.Random(seed)
    n_items = rng.randint(60, 160)
    acc_chunk = rng.choice([4, 8])
    drop = rng.uniform(0.0, 0.3)
    delay = rng.uniform(0.0, 0.3)
    patience = rng.randint(8, 12)
    rec = Recorder(per_item_sleep=rng.uniform(0.5, 2.0) * 1e-4)
    rt = HeteroRuntime()
    unit, _worker = heartbeat_loopback_unit(
        "r0", heartbeat=0.02, patience=patience,
        hb_seed=seed * 31 + 7, drop=drop, delay=delay, max_delay=0.01)
    rt.register_unit("r0", WorkerKind.CC, work_fn=rec, backend=unit)
    rt.register_unit("cc0", WorkerKind.CC, work_fn=rec)
    rep = rt.parallel_for(num_items=n_items, policy="multidynamic",
                          engine="interrupt", acc_chunk=acc_chunk)
    return rep, rec, n_items


class TestHeartbeatFaultBattery:
    """≥20 seeded heartbeat-only fault schedules: dropped/delayed
    liveness frames must never convict a slow-but-alive worker, and a
    truly dead worker is always exact-once."""

    @given(seed=st.integers(0, 10**6))
    @settings(max_examples=20, deadline=None)
    def test_no_false_conviction_under_heartbeat_faults(self, seed):
        rep, rec, n_items = heartbeat_battery_run(seed)
        assert rep.items == n_items
        assert_exact_tiling(rep.coverage, n_items)
        rec.assert_exactly_once(n_items)
        bad = [e for e in (rep.events or [])
               if e["action"] in ("dead", "lost")]
        assert not bad, f"false conviction of a live worker: {bad}"
        times = [e["t"] for e in (rep.events or [])]
        assert times == sorted(times), "events not monotone"

    @given(seed=st.integers(0, 10**6))
    @settings(max_examples=20, deadline=None)
    def test_true_death_is_exact_once_every_seed(self, seed):
        import random

        rng = random.Random(seed)
        n_items = rng.randint(60, 160)
        acc_chunk = rng.choice([4, 8])
        client_end, worker_end = LoopbackTransport.pair()
        worker = RemoteWorker(_PartitionOnWork(worker_end),
                              poll_interval=0.02)
        threading.Thread(target=worker.serve, daemon=True).start()
        rec = Recorder(per_item_sleep=1e-5)
        rt = HeteroRuntime()
        rt.register_unit(
            "r0", WorkerKind.CC, work_fn=rec,
            backend=RemoteUnit("r0", transport=client_end,
                               retry_interval=0.05, max_retries=600,
                               heartbeat=0.02, patience=rng.randint(2, 5)))
        rt.register_unit("cc0", WorkerKind.CC, work_fn=rec)
        rep = rt.parallel_for(num_items=n_items, policy="multidynamic",
                              engine="interrupt", acc_chunk=acc_chunk)
        assert rep.items == n_items
        assert_exact_tiling(rep.coverage, n_items)
        rec.assert_exactly_once(n_items)  # strict: dead unit ran nothing
        dead = [e for e in rep.events if e["action"] == "dead"]
        assert len(dead) == 1 and dead[0]["unit"] == "r0"


# ---------------------------------------------------------------------------
# lifecycle bug batch: close() idempotence, bye warnings, pump resilience
# ---------------------------------------------------------------------------
class _ByeFailsTransport(FrameTap):
    """Raises on the graceful bye (a worker that died first)."""

    def _forward(self, frame):
        if frame.get("kind") == "bye":
            raise TransportError("injected: peer already gone")
        self.inner.send(frame)


class _FlakyPumpWorker(RemoteWorker):
    """First two completion-pump passes die with an unexpected error —
    the regression shape: an exception on the done-posting path."""

    _faults = 2

    def _pump_once(self):
        if self._faults > 0:
            self._faults -= 1
            raise RuntimeError("injected pump fault")
        super()._pump_once()


class _DoneSendRaises(FlakyTransport):
    """Worker-side medium whose send *raises* on completion frames —
    both the original and the stripped resend fail."""

    def __init__(self, inner):
        super().__init__(inner, seed=0)

    def send(self, frame):
        if isinstance(frame, dict) and frame.get("kind") in ("done",
                                                             "done_batch"):
            raise RuntimeError("injected send-path fault")
        self.inner.send(frame)


class TestLifecycleBugBatch:
    def test_close_is_idempotent(self):
        unit, tap, _worker = tapped_loopback_unit("r0")
        rec = Recorder()
        _drive_direct(unit, [Chunk(0, 8, "r0")], rec)  # closes once
        unit.close()
        unit.close()
        assert len(tap.frames("bye")) == 1, (
            "a second close() re-sent bye on a closed session"
        )

    def test_failed_bye_is_logged_not_swallowed(self, caplog):
        unit, _tap, _worker = tapped_loopback_unit(
            "r0", tap_cls=_ByeFailsTransport)
        rec = Recorder()
        bus = CompletionBus()
        unit.start(bus)
        unit.submit(Chunk(0, 8, "r0"), rec)
        deadline = time.perf_counter() + 10.0
        recs = []
        while not recs and time.perf_counter() < deadline:
            bus.wait(timeout=0.2)
            recs = bus.drain()
        assert recs and recs[0].error is None
        import logging
        with caplog.at_level(logging.WARNING, logger="repro.core.transport"):
            unit.close()
        assert any("graceful bye failed" in r.message for r in caplog.records)
        unit.close()  # still idempotent after the failure path

    def test_pump_exception_does_not_drop_completions(self):
        # the pump's first passes die; the completion must still arrive
        # (guard keeps the thread alive; the done cache makes the item
        # recoverable) instead of the old silent-stall behavior
        client_end, worker_end = LoopbackTransport.pair()
        worker = _FlakyPumpWorker(worker_end, poll_interval=0.02)
        threading.Thread(target=worker.serve, daemon=True).start()
        unit = RemoteUnit("r0", transport=client_end,
                          retry_interval=0.05, max_retries=100)
        rec = Recorder()
        recs = _drive_direct(unit, [Chunk(i * 8, (i + 1) * 8, "r0")
                                    for i in range(5)], rec)
        assert len(recs) == 5
        assert not any(r.error for r in recs)
        rec.assert_exactly_once(40)

    def test_done_send_failure_ends_session_deliberately(self):
        # when even the stripped completion cannot be sent, the worker
        # must end the session (definitive EOF -> WorkerLost -> requeue)
        # instead of leaving a half-dead session that answers busy
        # probes forever while never delivering a completion
        client_end, worker_end = LoopbackTransport.pair()
        worker = RemoteWorker(_DoneSendRaises(worker_end),
                              poll_interval=0.02)
        threading.Thread(target=worker.serve, daemon=True).start()
        rec = Recorder(per_item_sleep=1e-5)
        rt = HeteroRuntime()
        rt.register_unit("r0", WorkerKind.CC, work_fn=rec,
                         backend=RemoteUnit("r0", transport=client_end,
                                            retry_interval=0.05,
                                            max_retries=600))
        rt.register_unit("cc0", WorkerKind.CC, work_fn=rec)
        t0 = time.perf_counter()
        rep = rt.parallel_for(num_items=100, policy="multidynamic",
                              engine="interrupt", acc_chunk=8)
        wall = time.perf_counter() - t0
        assert rep.items == 100
        assert_exact_tiling(rep.coverage, 100)
        assert set(rec.counts) == set(range(100))  # at-least-once boundary
        lost = [e for e in rep.events if e["action"] == "lost"]
        assert len(lost) == 1 and lost[0]["unit"] == "r0"
        assert wall < 15.0, (
            f"run took {wall:.1f}s — the dead session was not ended "
            "deliberately"
        )
