"""Host-level wait-n-f peer exchange: TCP frames + the native MRMW register.

This is the true *asynchronous* DCN path the on-mesh seeded-subset emulation
stands in for (SURVEY §2.3 asynchrony row): across OS processes/hosts, each
peer PUBLISHES its per-step payload (serialized gradient/model delta) to
everyone, and ``collect`` returns as soon as the **q = n - f fastest** peers'
payloads for that step have arrived — real arrival order, real straggler
tolerance, like ``Server.get_gradients``'s wait-n-f path
(pytorch_impl/libs/garfieldpp/server.py:134-155).

Reference counterparts re-designed here:
  - T1 gRPC ``MessageExchange`` (tensorflow_impl/libs/garfield.proto:3-10):
    replaced by length-prefixed frames over plain TCP. The payloads are
    opaque bytes at THIS layer; the cluster driver's data frames carry the
    typed codec of ``utils.wire`` (16-byte self-describing header + f32 or
    bf16 payload, DESIGN.md §11) where the reference shipped bare
    ``ndarray.tobytes()`` (garfield.proto:24-33) — bf16 halves every frame
    on the DCN and the header's crc/dtype/count make corrupted bytes ban
    evidence instead of undetectable GAR input.
  - T2 history servicer (grpc_message_exchange_servicer.py:51-86): readers
    there spin-poll the history list at 1 ms; here the per-peer mailbox is
    the native ``MultiBuffer`` MRMW register (T9,
    native/src/multibuffer.cpp), whose ``read(slot, min_version)`` BLOCKS on
    a condvar — no polling. The register's last-writer-wins slot + version
    counter is exactly the iteration-indexed rendezvous the servicer's
    history implements with lists and sleeps.

Wire format per frame: ``!IQQ`` header (peer_id, step, nbytes) + payload.
The peer-id field's high byte is the **plane tag** (DESIGN.md §15): an
exchange built with ``planes=P`` carries P independent register slots per
peer (one ``MultiBuffer`` slot per (peer, plane)), so protocols that used
to multiplex several logical planes through one last-writer-wins slot —
LEARN's gossip interleaved gradients and models as steps 2i+2/2i+3 —
instead publish each plane to its own slot and a slow consumer of one
plane can no longer lose frames to the other's overwrites. Plane 0 is
the default everywhere, so single-plane deployments (and their committed
trajectories) are untouched; the typed payloads of ``utils.wire`` carry
the same plane tag in their codec header's spare bits, making the frames
self-describing end to end.

Slot payloads are stored as ``!Q`` step + payload so ``collect`` only
accepts the exact step it asked for — the register is last-writer-wins, so
a publisher racing ahead overwrites older frames and a reader that missed
one times out for that peer instead of mixing iterations. Collect each
step before peers publish the next (the bulk-synchronous round structure
every topology here has).
"""

import functools
import queue
import socket
import struct
import threading
import time

from ..native import MultiBuffer
from ..telemetry import trace as _trace

__all__ = ["PeerExchange", "RoundCollector"]

_HDR = struct.Struct("!IQQ")
_SLOT = struct.Struct("!Q")
# Plane tag in the transport header: high byte of the u32 peer-id field
# (peer counts are tiny; 2^24 ranks is far beyond any deployment).
_PLANE_SHIFT = 24
_PEER_MASK = (1 << _PLANE_SHIFT) - 1


def _emit_wait(step, q, arrived, wait_s, timed_out=False, plane=0):
    """Report one wait-n-f quorum wait to the telemetry plane.

    Goes through the process-global hook (telemetry.hub.emit_event), a
    no-op when no MetricsHub is installed — un-telemetered deployments
    pay one cached-import dict lookup per collect. These events are the
    host-side latency ground truth the on-mesh seeded-subset emulation
    has no access to (docs/TELEMETRY.md). ``plane`` tags which exchange
    plane the wait served (schema v6) so multi-plane protocols' latencies
    attribute per plane instead of blurring together."""
    from ..telemetry import hub as _tele_hub

    _tele_hub.emit_event(
        "exchange_wait", step=int(step), q=int(q), arrived=int(arrived),
        wait_s=round(float(wait_s), 6), timed_out=bool(timed_out),
        plane=int(plane),
    )


def _emit_send_drop(peer, step):
    """Report one publisher-side drop-oldest (sender-queue overflow) to
    the telemetry plane. Without this event the backpressure was SILENT —
    a hung receiver aging frames out of its sender queue looked identical
    to a healthy run from the publisher's telemetry (the receive-side
    ``plane_drop`` twin of this event covers the other direction)."""
    from ..telemetry import hub as _tele_hub

    _tele_hub.emit_event(
        "send_queue_drop", peer=int(peer), step=int(step)
    )

# Slot frame with this step value is the close sentinel: it wakes every
# reader blocked in the native register so close() can join them BEFORE
# freeing the buffer — freeing with a blocked waiter inside
# gt_multibuffer_wait is a use-after-free on the condvar.
_CLOSE_STEP = 2 ** 64 - 1


def _recv_exact(conn, n):
    buf = bytearray()
    while len(buf) < n:
        chunk = conn.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("peer closed mid-frame")
        buf.extend(chunk)
    return bytes(buf)


class PeerExchange:
    """All-to-all publish/collect among ``len(hosts)`` peers.

    ``hosts``: list of "ip:port" endpoints, one per peer; this process binds
    ``hosts[my_index]``. Peers that are down or slow simply do not count
    toward the quorum — ``collect`` waits for the q fastest, which is the
    entire Byzantine-tolerance contract of the reference's async path.
    """

    def __init__(self, my_index, hosts, *, accept_timeout_ms=100,
                 connect_retry_ms=10_000, reconnect_timeout_ms=1_000,
                 send_timeout_ms=5_000, send_queue_frames=4, planes=1):
        self.my_index = int(my_index)
        self.hosts = list(hosts)
        self.n = len(self.hosts)
        self.planes = int(planes)
        if not 1 <= self.planes <= 16:
            raise ValueError(f"planes must be in [1, 16], got {planes}")
        self.connect_retry_ms = connect_retry_ms
        self.reconnect_timeout_ms = reconnect_timeout_ms
        self.send_timeout_ms = send_timeout_ms
        self.send_queue_frames = send_queue_frames
        # One register slot per (peer, plane): plane p's slots occupy
        # [p*n, (p+1)*n) — see _slot. Plane 0 is the classic layout.
        self._mb = MultiBuffer(self.n * self.planes)
        self._send_socks = {}
        self._connect_attempted = set()  # peers whose startup grace is spent
        self._send_lock = threading.Lock()
        self._senders = {}       # per-peer sender threads + queues (lazy)
        self._closing = threading.Event()
        self._waiters = []       # collect()'s reader threads, joined at close
        self._conns = []         # inbound connections, closed at close
        self._peer_threads = []  # inbound reader threads (they mb.write)
        self._conns_lock = threading.Lock()
        # Per-peer watcher registry (the symmetric-teardown contract of
        # remove_peer): every live registration watching peer idx's slots
        # — collect_begin waiters, read_latest_begin latches AND
        # RoundCollector watchers — records (cancel_callable, thread)
        # here so a churn leave / Byzantine ban retires them ALL at once.
        # Dead threads are pruned lazily on registration and removal.
        self._peer_watchers = {}
        self._watchers_lock = threading.Lock()

        ip, _, port = self.hosts[self.my_index].rpartition(":")
        self._server = socket.create_server(
            (ip or "0.0.0.0", int(port)), reuse_port=False
        )
        self._server.settimeout(accept_timeout_ms / 1000.0)
        self._accept_thread = threading.Thread(
            target=self._accept_loop, daemon=True
        )
        self._accept_thread.start()

    # --- receive side ------------------------------------------------------

    def _accept_loop(self):
        while not self._closing.is_set():
            try:
                conn, _ = self._server.accept()
            except socket.timeout:
                continue
            except OSError:
                break
            t = threading.Thread(
                target=self._peer_loop, args=(conn,), daemon=True
            )
            with self._conns_lock:
                self._conns.append(conn)
                self._peer_threads.append(t)
            t.start()

    def _slot(self, idx, plane=0):
        """Register slot of (peer ``idx``, ``plane``)."""
        return plane * self.n + idx

    def _check_plane(self, plane):
        """Loud capacity guard for every plane-taking entry point: the
        plane/shard tag rides a spare nibble end to end (transport
        header high byte here, wire codec header nibble — DESIGN.md
        §15/§19), so an out-of-range id must fail at the CALL SITE that
        would stamp it. Silently truncating (or indexing a register
        slot past ``n * planes``) would deliver one shard's frames into
        another shard's fold — the exact corruption the shard stamp
        exists to make attributable."""
        if isinstance(plane, bool) or not isinstance(plane, int):
            raise TypeError(
                f"plane/shard tag must be an integer, got {plane!r}"
            )
        if not 0 <= plane < self.planes:
            raise ValueError(
                f"plane/shard tag {plane} out of range for a "
                f"{self.planes}-plane exchange (build with planes=P to "
                "widen, max 16 — the wire header nibble)"
            )
        return plane

    def _peer_loop(self, conn):
        try:
            while not self._closing.is_set():
                tagged, step, nbytes = _HDR.unpack(
                    _recv_exact(conn, _HDR.size)
                )
                payload = _recv_exact(conn, nbytes)
                peer_id = tagged & _PEER_MASK
                plane = tagged >> _PLANE_SHIFT
                # A plane this exchange was not built with is dropped like
                # an out-of-range peer id: mixed-plane deployments must
                # not corrupt a foreign slot.
                if 0 <= peer_id < self.n and plane < self.planes:
                    self._mb.write(
                        self._slot(peer_id, plane), _SLOT.pack(step) + payload
                    )
        except (ConnectionError, OSError):
            pass  # peer gone: its slot simply stops advancing
        finally:
            try:
                conn.close()
            except OSError:
                pass

    # --- per-peer watcher registry (symmetric teardown) --------------------

    def _register_watcher(self, idx, cancel, thread):
        """Record a live registration watching peer ``idx``'s slots so
        ``remove_peer`` can retire it; prunes finished entries."""
        with self._watchers_lock:
            entries = self._peer_watchers.setdefault(int(idx), [])
            entries[:] = [e for e in entries if e[1].is_alive()]
            entries.append((cancel, thread))

    def remove_peer(self, idx):
        """Retire EVERY live watcher on peer ``idx``'s slots — collect
        waiters, ``read_latest_begin`` latches and ``RoundCollector``
        watchers alike — the churn-leave / Byzantine-ban teardown.

        Before this existed the teardown was ASYMMETRIC: a membership
        change cancelled the round collector's watcher for the departed
        peer, but any ``read_latest_begin`` latch registered on the same
        peer kept its thread (and its eager-decode transform) alive until
        the harvest deadline or ``close()`` — a slow leak on every churn
        leave, pinned by tests/test_exchange.py. Cancellation here is
        idempotent and joins each watcher briefly so the caller observes
        the threads actually gone.
        """
        with self._watchers_lock:
            entries = self._peer_watchers.pop(int(idx), [])
        for cancel, t in entries:
            try:
                cancel()
            except Exception:  # noqa: BLE001 — teardown must not raise
                pass
        for _, t in entries:
            if t is not threading.current_thread():
                t.join(timeout=5)

    # --- send side ---------------------------------------------------------

    def _sock_for(self, idx):
        """Cached connection to peer idx.

        Only the FIRST-ever connect to a peer gets the long
        ``connect_retry_ms`` grace — peers come up in arbitrary order and a
        publish must not lose its frame to a listener that is still binding
        (the reference's pull loops retry the same way, server.py:138-141).
        RE-connects (the cached socket died, i.e. the peer crashed or
        restarted) make one short ``reconnect_timeout_ms`` attempt instead:
        a crashed receiver must not cost its sender thread the full grace
        window on every frame. The default (1 s) leaves room for WAN
        connect RTTs; an UNREACHABLE (not merely refused — refusal is
        instant) peer costs its OWN sender thread at most that much per
        frame (other peers' sends are unaffected — per-peer threads).

        Once connected, the socket's timeout is reset to ``send_timeout_ms``
        — the connect timeout must NOT govern ``sendall`` (a multi-MB model
        frame cannot ship inside the short reconnect window), while a hung
        (not crashed) receiver still cannot block publish forever.
        """
        with self._send_lock:
            sock = self._send_socks.get(idx)
        if sock is not None:
            return sock
        ip, _, port = self.hosts[idx].rpartition(":")
        if idx in self._connect_attempted:
            sock = socket.create_connection(
                (ip, int(port)), timeout=self.reconnect_timeout_ms / 1000.0
            )
        else:
            self._connect_attempted.add(idx)
            deadline = time.monotonic() + self.connect_retry_ms / 1000.0
            while True:
                try:
                    sock = socket.create_connection(
                        (ip, int(port)), timeout=5
                    )
                    break
                except OSError:
                    if (time.monotonic() >= deadline
                            or self._closing.is_set()):
                        raise
                    time.sleep(0.05)
        sock.settimeout(self.send_timeout_ms / 1000.0)
        with self._send_lock:
            self._send_socks[idx] = sock
        return sock

    def _sender_loop(self, idx, q):
        """Per-peer sender: owns the connection to ``idx``, drains ``q`` in
        FIFO order (TCP ordering per peer is preserved), drops frames for a
        dead receiver. A ``None`` item is the close sentinel."""
        while True:
            frame = q.get()
            if frame is None:
                break
            # NOTE: frames queued before close() are still sent (the close
            # sentinel sits behind them in FIFO order) — the PS's final
            # stop frame must not be dropped by an immediate close.
            try:
                self._sock_for(idx).sendall(frame)
            except OSError:
                with self._send_lock:
                    sock = self._send_socks.pop(idx, None)
                if sock is not None:
                    try:
                        sock.close()
                    except OSError:
                        pass

    def _sender_for(self, idx):
        s = self._senders.get(idx)
        if s is None:
            q = queue.Queue(maxsize=self.send_queue_frames)
            t = threading.Thread(
                target=self._sender_loop, args=(idx, q), daemon=True
            )
            t.start()
            s = self._senders[idx] = (q, t)
        return s

    def publish(self, step, payload, *, to=None, plane=0):
        """Send (step, payload) to every peer (or just ``to``); deposit
        locally too. ``plane`` routes the frame to that plane's register
        slots on every receiver (DESIGN.md §15) — plane 0 is the classic
        single-plane layout.

        Sends go through PER-PEER sender threads with bounded FIFO queues
        (VERDICT r3 weak #4): one hung — not crashed — receiver used to
        hold the shared send lock for ``send_timeout_ms`` per step and
        stall every other peer's publish; now it only backs up its own
        queue, and when that overflows the OLDEST frame for that peer is
        dropped (the register is last-writer-wins anyway — a receiver that
        slow would age the frame out on arrival). Unreachable peers are
        skipped: a publisher must not block on a crashed receiver (the
        reference's async sends are fire-and-forget RPCs, server.py:127).
        ``to`` narrows the fan-out — e.g. workers in the cluster driver
        send gradients only to the PS, like the reference's point-to-point
        RPC pulls.
        """
        payload = bytes(payload)
        plane = self._check_plane(plane)
        targets = range(self.n) if to is None else to
        with _trace.span(
            "publish", step=int(step), nbytes=len(payload), plane=plane,
            fanout=len(targets) if to is not None else self.n - 1,
        ):
            self._mb.write(
                self._slot(self.my_index, plane), _SLOT.pack(step) + payload
            )
            frame = _HDR.pack(
                self.my_index | (plane << _PLANE_SHIFT), step, len(payload)
            ) + payload
            for idx in targets:
                if idx == self.my_index:
                    continue
                q, _ = self._sender_for(idx)
                while True:
                    try:
                        q.put_nowait(frame)
                        break
                    except queue.Full:
                        try:
                            # drop the oldest frame for this peer.
                            # ``step`` is the frame being ENQUEUED, not
                            # the dropped one (the dropped frame's step
                            # is gone with its bytes) — close enough to
                            # localize the backpressure in the stream.
                            q.get_nowait()
                            _emit_send_drop(idx, step)
                        except queue.Empty:
                            pass

    # --- collect (wait-n-f) ------------------------------------------------

    def _wait_slot(self, idx, step, deadline_box, results, sem,
                   transform=None, cancel=None, plane=0):
        """Block on the native register until peer idx publishes ``step``.

        Only the EXACT step joins the quorum: the register is
        last-writer-wins, so if the peer already overwrote ``step`` with a
        newer frame (got_step > step) the requested payload is gone — the
        waiter gives up rather than hand a different iteration's data to
        the aggregation. ``deadline_box[0]`` is None until the caller's
        ``wait()`` arms it (collect_begin semantics: frames latch from
        registration, the timeout clock starts at harvest); reads run in
        1 s chunks (armed or not) so arming — and ``cancel`` — take
        effect promptly. Intermediate older frames do not restart the
        deadline.

        ``cancel`` is the registration's lifecycle event: a role shutting
        down (or changing membership) mid-registration sets it and the
        waiter exits within one read chunk instead of lingering until the
        deadline or ``close()`` — the thread-leak fix pinned by
        tests/test_exchange.py.

        ``transform`` runs HERE, in the waiter thread, the moment the
        frame lands — this is the eager-decode hook the cluster driver
        uses to overlap wire decode (+ H2D staging) with the other peers'
        receives and the local device step, instead of decoding the whole
        quorum serially after it closes. A transform that raises has its
        exception STORED as the peer's result (not re-raised): on the
        quorum paths a failed decode is Byzantine ban evidence the caller
        must see attributed to its rank, not a missing-peer timeout.
        """
        version = 0
        try:
            while not self._closing.is_set() and not (
                cancel is not None and cancel.is_set()
            ):
                deadline = deadline_box[0]
                if deadline is None:
                    chunk_ms = 1_000
                else:
                    chunk_ms = int((deadline - time.monotonic()) * 1000)
                    if chunk_ms <= 0:
                        break
                try:
                    version, raw = self._mb.read(
                        self._slot(idx, plane), min_version=version + 1,
                        timeout_ms=min(max(chunk_ms, 1), 1_000),
                    )
                except TimeoutError:
                    continue  # chunk expired: re-check deadline/closing
                (got_step,) = _SLOT.unpack_from(raw)
                if got_step == _CLOSE_STEP:  # woken by close()
                    break
                if got_step == step:
                    payload = raw[_SLOT.size:]
                    if transform is not None:
                        # The eager decode+H2D runs HERE, on the waiter
                        # thread — the span keeps it on its own trace
                        # track so the report shows the overlap.
                        with _trace.span(
                            "decode", step=int(step), peer=int(idx),
                            nbytes=len(payload),
                        ):
                            try:
                                payload = transform(idx, payload)
                            except Exception as exc:  # noqa: BLE001
                                payload = exc
                    results[idx] = payload
                    break
                if got_step > step:  # requested step already overwritten
                    break
        finally:
            sem.release()

    def collect_begin(self, step, q, *, timeout_ms=30_000, peers=None,
                      transform=None, batch_transform=None, plane=0):
        """Register the waiters for ``step`` NOW; harvest with ``.wait()``.

        ``batch_transform`` (mutually exclusive with ``transform``) is
        the BULK decode hook (ISSUE 20): waiters latch raw frames, and
        the harvest hands every latched frame to one
        ``batch_transform(items)`` call — ``items`` a list of
        ``(peer_index, payload)`` pairs in peer order, returning one
        result per item (store an exception instance, e.g. a WireError,
        to attribute a reject to its sender exactly like a raising
        per-frame ``transform``). A multi-frame quorum then takes ONE
        vectorized trip through ``wire.decode_batch_into`` (e.g.
        ``StreamingAggregator.wire_batch_transform``) instead of a
        Python codec trip per frame. The exchange stays codec-agnostic:
        frames are opaque bytes here, the hook owns the decode. The
        trade against ``transform`` is overlap: per-frame transforms run
        eagerly in waiter threads as frames land, the batch hook runs at
        harvest — profitable exactly when per-frame Python overhead
        exceeds the lost overlap (the 10^6-client ingest regime;
        fed_bench's ``ingest_micro`` check brackets the crossover).

        Symmetric all-to-all protocols (LEARN gossip) need this split: with
        plain publish-then-``collect``, the moment the last node's frame
        lands every peer's quorum completes and they publish the NEXT
        phase — overwriting the last-writer-wins slots in the window
        between that node's publish and its collect registration (a whole
        scheduler quantum on an oversubscribed host; observed dropping a
        healthy node at round 3 on the 1-core CI box). Registering the
        round's waiters BEFORE the local compute closes the window: frames
        that arrive while this node still works are latched by the already-
        blocked readers and cannot be lost. The ``timeout_ms`` clock starts
        at ``wait()`` — NOT here — so arbitrarily long local work (a first
        eval's compile) between registration and harvest cannot eat the
        quorum budget.

        The returned harvest exposes ``wait.cancel()``: a registration a
        role will never harvest (shutdown, membership change, a round
        abandoned by a catch-up jump) MUST be cancelled so its waiter
        threads exit within one read chunk instead of lingering until
        ``close()`` — harvesting also auto-cancels whatever waiters are
        still pending once it returns (tests/test_exchange.py pins both).
        """
        if step >= _CLOSE_STEP:
            raise ValueError(f"step {step} reserved for the close sentinel")
        if transform is not None and batch_transform is not None:
            raise ValueError(
                "transform and batch_transform are mutually exclusive: "
                "per-frame eager decode and harvest-time batch decode "
                "are different overlap strategies — pick one"
            )
        plane = self._check_plane(plane)
        peers = list(range(self.n)) if peers is None else list(peers)
        if q > len(peers):
            raise ValueError(f"q={q} exceeds the {len(peers)} waited peers")
        results = {}
        sem = threading.Semaphore(0)
        deadline_box = [None]  # armed by wait()
        # Per-PEER cancel events (not one shared event): remove_peer must
        # retire exactly the departed peer's waiter while the rest of the
        # registration keeps collecting. cancel_all (the harvest/teardown
        # path) sets every one.
        peer_cancels = {}
        # Prune finished waiters from earlier collects — without this a long
        # run retains O(steps * n) dead Thread objects until close().
        self._waiters = [t for t in self._waiters if t.is_alive()]
        for idx in peers:
            ev = peer_cancels[idx] = threading.Event()
            t = threading.Thread(
                target=self._wait_slot,
                args=(idx, step, deadline_box, results, sem, transform,
                      ev, plane),
                daemon=True,
            )
            self._waiters.append(t)
            t.start()
            self._register_watcher(idx, ev.set, t)

        def cancel_all():
            for ev in peer_cancels.values():
                ev.set()

        def harvest(out):
            # Batch decode at harvest time (``batch_transform`` above):
            # ONE hook call over every latched frame, per-peer results
            # back in place — an exception instance in the result list
            # stays that peer's stored ban evidence, and a hook that
            # dies wholesale attributes the same evidence to every
            # frame it was handed (the caller sees it per peer either
            # way, never a silent drop).
            if batch_transform is None or not out:
                return out
            items = sorted(out.items())
            with _trace.span("decode", step=int(step), plane=int(plane),
                             frames=len(items),
                             nbytes=sum(len(p) for _, p in items)):
                try:
                    res = list(batch_transform(items))
                except Exception as exc:  # noqa: BLE001
                    return {i: exc for i, _ in items}
            if len(res) != len(items):
                raise RuntimeError(
                    f"batch_transform returned {len(res)} results for "
                    f"{len(items)} frames — the per-frame attribution "
                    "contract needs exactly one result per frame"
                )
            return {i: r for (i, _), r in zip(items, res)}

        def wait():
            # Every waiter releases exactly once (success, give-up, or
            # deadline); keep draining until the quorum is met or all
            # waited slots are accounted for — a timed-out straggler must
            # not mask a still-pending success. The grace on the final
            # acquires covers waiters oversleeping one unarmed 1 s chunk.
            t0 = time.monotonic()
            deadline_box[0] = t0 + timeout_ms / 1000.0
            hard = deadline_box[0] + 2.0
            sp = _trace.span(
                "collect", step=int(step), q=int(q), plane=int(plane)
            )
            try:
                with sp:
                    for _ in range(len(peers)):
                        if not sem.acquire(
                            timeout=max(hard - time.monotonic(), 0.1)
                        ):
                            break
                        if len(results) >= q:
                            sp.set(arrived=len(results))
                            _emit_wait(
                                step, q, len(results),
                                time.monotonic() - t0, plane=plane,
                            )
                            return harvest(dict(results))
                    if len(results) >= q:
                        sp.set(arrived=len(results))
                        _emit_wait(
                            step, q, len(results), time.monotonic() - t0,
                            plane=plane,
                        )
                        return harvest(dict(results))
                    sp.set(arrived=len(results), timed_out=True)
                    _emit_wait(
                        step, q, len(results), time.monotonic() - t0,
                        timed_out=True, plane=plane,
                    )
                    raise TimeoutError(
                        f"only {len(results)}/{q} peers reached step {step} "
                        f"within {timeout_ms} ms"
                    )
            finally:
                # Single-harvest contract: whatever waiters are still
                # blocked (beyond-quorum slots, give-ups in flight) are
                # released now instead of at their deadline.
                cancel_all()

        wait.cancel = cancel_all
        return wait

    def collect(self, step, q, *, timeout_ms=30_000, peers=None,
                transform=None, batch_transform=None, plane=0):
        """Payloads of the q fastest peers (self included) at ``step``.

        Returns a dict {peer_index: payload} with >= q entries, or raises
        TimeoutError if fewer than q peers published within ``timeout_ms``
        — the bounded-retry exit of the reference (ps.py:84-88 gives up
        after 10 retries and exits). ``peers`` restricts the wait to a
        subset of slots — e.g. the PS waits on worker slots only (gradient
        plane) while workers wait on the PS slot only (model plane), so
        both planes share one exchange without cross-talk. For symmetric
        protocols use ``collect_begin`` (see its docstring for the
        publish-then-collect race it closes). ``transform`` is the eager
        per-frame decode hook (see ``_wait_slot``); ``batch_transform``
        the harvest-time bulk decode hook (see ``collect_begin``).
        """
        return self.collect_begin(
            step, q, timeout_ms=timeout_ms, peers=peers, transform=transform,
            batch_transform=batch_transform, plane=plane,
        )()

    def read_latest_begin(self, idx, min_step, *, transform=None, plane=0):
        """Register a watcher on peer ``idx``'s slot NOW; harvest the
        newest (step, payload) with step >= ``min_step`` via the returned
        ``wait(timeout_ms)``.

        The pre-registered twin of ``read_latest``, built for the SSMW
        worker's model plane: registering BEFORE the local gradient
        compute means the PS's next model frame is latched (and, with
        ``transform``, wire-decoded + device-staged) the moment it lands
        — while this worker is still inside its own device step — instead
        of being discovered, decoded and uploaded serially afterwards.
        The watcher keeps latching NEWER satisfying frames until harvest,
        so the catch-up semantics survive: a straggler that computes
        through several PS rounds harvests the newest model, exactly like
        a fresh ``read_latest`` would. Transform failures are stored as
        the payload (see ``_wait_slot``); the harvest's timeout clock
        starts at ``wait()``, not here. A harvest that times out retires
        the watcher (re-register to keep waiting), and ``wait.cancel()``
        retires it WITHOUT harvesting — the role-shutdown lifecycle
        contract shared with ``collect_begin``.
        """
        plane = self._check_plane(plane)
        state = {"best": None}
        cond = threading.Condition()
        harvested = threading.Event()

        def watch():
            version = 0
            while not (self._closing.is_set() or harvested.is_set()):
                try:
                    version, raw = self._mb.read(
                        self._slot(idx, plane), min_version=version + 1,
                        timeout_ms=500,
                    )
                except TimeoutError:
                    continue
                (got_step,) = _SLOT.unpack_from(raw)
                if got_step == _CLOSE_STEP:
                    break
                if got_step >= min_step:
                    payload = raw[_SLOT.size:]
                    if transform is not None:
                        with _trace.span(
                            "decode", step=int(got_step), peer=int(idx),
                            nbytes=len(payload),
                        ):
                            try:
                                payload = transform(idx, payload)
                            except Exception as exc:  # noqa: BLE001
                                payload = exc
                    with cond:
                        state["best"] = (got_step, payload)
                        cond.notify_all()

        t = threading.Thread(target=watch, daemon=True)
        self._waiters = [w for w in self._waiters if w.is_alive()]
        self._waiters.append(t)
        t.start()
        # Symmetric teardown (remove_peer docstring): the latch is a peer
        # watcher like any collect waiter — a churn leave retires it too.
        self._register_watcher(idx, harvested.set, t)

        def wait(timeout_ms=30_000):
            deadline = time.monotonic() + timeout_ms / 1000.0
            sp = _trace.span(
                "latest_wait", step=int(min_step), peer=int(idx),
            )
            with sp:
                with cond:
                    while state["best"] is None:
                        remaining = deadline - time.monotonic()
                        if remaining <= 0 or self._closing.is_set():
                            break
                        cond.wait(timeout=min(remaining, 1.0))
                    best = state["best"]
                harvested.set()  # stop latching; watcher exits on its own
                if best is None:
                    sp.set(timed_out=True)
                    raise TimeoutError(
                        f"peer {idx} did not reach step {min_step} within "
                        f"{timeout_ms} ms"
                    )
                sp.set(got=int(best[0]))
                return best

        wait.cancel = harvested.set
        return wait

    def round_collector(self, peers, *, transform=None, plane=0):
        """A ``RoundCollector`` over this exchange's ``peers`` slots on
        ``plane`` — the bounded-staleness quorum primitive (see the class
        docstring). A multi-plane protocol builds one collector per plane
        (LEARN async: gradients and gossip each get their own)."""
        return RoundCollector(self, peers, transform=transform, plane=plane)

    def read_latest(self, idx, min_step, *, timeout_ms=30_000, plane=0):
        """Newest (step, payload) in peer ``idx``'s slot with step >=
        ``min_step``.

        The catch-up read for consumers of a FAST producer: ``collect``'s
        exact-step contract is right for same-round quorums (gradients), but
        a straggler reading the PS's model slot must accept the newest
        round, not die because the one it expected was overwritten (the
        last-writer-wins register keeps only the latest frame). Returns as
        soon as the current or a newly-written frame satisfies the bound;
        raises TimeoutError otherwise.
        """
        plane = self._check_plane(plane)
        deadline = time.monotonic() + timeout_ms / 1000.0
        version = 0
        while not self._closing.is_set():
            remaining_ms = int((deadline - time.monotonic()) * 1000)
            if remaining_ms <= 0:
                break
            try:
                version, raw = self._mb.read(
                    self._slot(idx, plane), min_version=version + 1,
                    timeout_ms=remaining_ms,
                )
            except TimeoutError:
                break
            (got_step,) = _SLOT.unpack_from(raw)
            if got_step == _CLOSE_STEP:
                break
            if got_step >= min_step:
                return got_step, raw[_SLOT.size:]
        raise TimeoutError(
            f"peer {idx} did not reach step {min_step} within {timeout_ms} ms"
        )

    def close(self):
        """Orderly teardown: stop IO, WAKE every reader blocked in the
        native register (close sentinel per slot), join all threads that
        could still touch the register, and only then free it."""
        if self._closing.is_set():
            return
        self._closing.set()
        try:
            self._server.close()
        except OSError:
            pass
        with self._conns_lock:
            for c in self._conns:  # unblocks _peer_loop recv -> mb.write
                try:
                    c.close()
                except OSError:
                    pass
            self._conns.clear()
        # Graceful sender drain: the close sentinel queues BEHIND any
        # pending frames (a final stop frame published just before close
        # must still ship); a FULL queue (receiver hung) sheds its oldest
        # frames instead of blocking close, and a sender still stuck in
        # sendall is unblocked by the socket close after the bounded join.
        for sq, _ in self._senders.values():
            while True:
                try:
                    sq.put_nowait(None)
                    break
                except queue.Full:
                    try:
                        sq.get_nowait()
                    except queue.Empty:
                        pass
        for sq, t in self._senders.values():
            t.join(timeout=6)
        self._senders.clear()
        with self._send_lock:
            for sock in self._send_socks.values():
                try:
                    sock.close()
                except OSError:
                    pass
            self._send_socks.clear()
        for slot in range(self.n * self.planes):
            self._mb.write(slot, _SLOT.pack(_CLOSE_STEP))
        for t in self._waiters:
            t.join(timeout=5)
        self._waiters.clear()
        with self._conns_lock:
            peer_threads, self._peer_threads = self._peer_threads, []
        for t in peer_threads:
            t.join(timeout=5)
        self._accept_thread.join(timeout=5)
        self._mb.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class RoundCollector:
    """Round-tagged register view: pre-registered MULTI-round watchers.

    The bounded-staleness quorum primitive (DESIGN.md §14). One
    PERSISTENT watcher thread per peer latches EVERY frame version the
    native register delivers — round tag, payload (through the eager
    ``transform`` decode hook, like ``collect_begin``'s waiters), and a
    global arrival generation — into a host-side view that outlives any
    single round. ``gather(round, q, max_staleness=s)`` then blocks until

      1. at least ``q`` peers hold an ADMISSIBLE frame (tag within ``s``
         rounds of ``round`` — stale frames are REUSED across gathers
         instead of re-collected, which is what lets the consumer's round
         rate decouple from the slowest publisher), and
      2. at least one admissible frame is NEW since the previous harvest
         (``require_fresh``): without this floor the consumer could
         free-run on the same cached frames, re-applying identical data
         at host speed — bounded staleness throttles it to the fastest
         publisher's pace instead.

    Compared to per-round ``collect_begin`` registrations this also fixes
    the watcher lifecycle: no per-round thread churn, membership changes
    (``remove_peer`` on a ban or a leave, ``add_peer`` on a join) retire
    or start exactly one thread, and ``close()`` cancels everything
    deterministically. The watcher threads are registered in the owning
    exchange's waiter list so ``PeerExchange.close()`` joins them before
    freeing the native register (the use-after-free contract in
    ``close``'s docstring).

    At ``max_staleness=0`` a gather admits exact-round frames only — the
    synchronous wait-n-f contract — which is the host-plane half of the
    ``--max_staleness 0`` bitwise-equality guarantee.

    ``plane`` scopes the collector to one exchange plane (DESIGN.md §15):
    a protocol with several logical planes (LEARN async gossips gradients
    AND models) runs one collector per plane over the same peers, each
    watching its own register slots — the per-plane form of the old
    single-slot multiplexing this class could not serve.
    """

    def __init__(self, exchange, peers, *, transform=None, plane=0):
        self._ex = exchange
        self._transform = transform
        self._plane = int(plane)
        if not 0 <= self._plane < exchange.planes:
            raise ValueError(
                f"plane {plane} out of range for a {exchange.planes}-plane "
                "exchange"
            )
        self._cond = threading.Condition()
        self._frames = {}   # peer -> (step, payload, generation)
        self._gen = 0       # global arrival counter
        self._mark = 0      # newest generation consumed by a harvest
        self._threads = {}
        self._stops = {}
        for idx in peers:
            self.add_peer(idx)

    def peers(self):
        with self._cond:
            return sorted(self._threads)

    def newest(self):
        """Newest round tag across every cached frame, or None before
        any arrival — the SWARM CLOCK a lagging decentralized node reads
        to catch up (the gossip analog of the SSMW worker's read_latest
        jump): a node whose own round counter falls behind the swarm's
        newest tag by more than the staleness cutoff would become
        inadmissible to every peer, so it jumps instead of computing
        rounds nobody can use."""
        with self._cond:
            return max(
                (s for s, _, _ in self._frames.values()), default=None
            )

    def add_peer(self, idx):
        """Start (or restart) the watcher for peer ``idx`` — a JOIN in a
        churn scenario. Idempotent for already-watched peers."""
        idx = int(idx)
        with self._cond:
            if idx in self._threads and self._threads[idx].is_alive():
                return
            stop = threading.Event()
            t = threading.Thread(
                target=self._watch, args=(idx, stop), daemon=True
            )
            self._stops[idx] = stop
            self._threads[idx] = t
        # Same join-before-register-free contract as collect_begin waiters.
        self._ex._waiters = [
            w for w in self._ex._waiters if w.is_alive()
        ]
        self._ex._waiters.append(t)
        t.start()
        # Symmetric teardown: an exchange-level remove_peer (churn leave)
        # retires this watcher AND drops its cached frame, exactly like
        # the collector's own remove_peer.
        self._ex._register_watcher(
            idx, functools.partial(self._drop_peer, idx), t
        )

    def _drop_peer(self, idx):
        """Cancel + forget peer ``idx`` WITHOUT joining (the exchange's
        ``remove_peer`` joins after cancelling every registered watcher);
        returns the watcher thread, if any."""
        idx = int(idx)
        with self._cond:
            stop = self._stops.pop(idx, None)
            t = self._threads.pop(idx, None)
            self._frames.pop(idx, None)
            if stop is not None:
                # Under the lock: a watcher mid-decode re-checks this
                # before writing, so a removed peer's frame cannot be
                # resurrected by an in-flight arrival.
                stop.set()
        return t

    def remove_peer(self, idx):
        """Cancel peer ``idx``'s watcher and drop its cached frame — a
        LEAVE (or a Byzantine ban). The thread exits within one read
        chunk; joined here so membership changes never leak threads."""
        t = self._drop_peer(idx)
        if t is not None:
            t.join(timeout=5)

    def _watch(self, idx, stop):
        version = 0
        ex = self._ex
        slot = ex._slot(idx, self._plane)
        while not (stop.is_set() or ex._closing.is_set()):
            try:
                version, raw = ex._mb.read(
                    slot, min_version=version + 1, timeout_ms=200
                )
            except TimeoutError:
                continue
            (got_step,) = _SLOT.unpack_from(raw)
            if got_step == _CLOSE_STEP:
                break
            payload = raw[_SLOT.size:]
            if self._transform is not None:
                with _trace.span(
                    "decode", step=int(got_step), peer=int(idx),
                    nbytes=len(payload),
                ):
                    try:
                        payload = self._transform(idx, payload)
                    except Exception as exc:  # noqa: BLE001 — ban evidence
                        payload = exc
            with self._cond:
                if stop.is_set():
                    break  # removed while decoding: drop, don't resurrect
                self._gen += 1
                self._frames[idx] = (got_step, payload, self._gen)
                self._cond.notify_all()

    def gather(self, round_, q, *, max_staleness=0, timeout_ms=30_000,
               require_fresh=True):
        """Admissible frames for ``round_``: ``{peer: (tag, payload)}``.

        Blocks until >= ``q`` peers hold a frame tagged within
        ``max_staleness`` rounds of ``round_`` and (``require_fresh``) at
        least one of them arrived since the previous harvest; returns ALL
        admissible frames (the caller picks the freshest ``q`` — ties
        break on rank for deterministic composition). Payloads may be
        stored transform exceptions — Byzantine ban evidence the caller
        must attribute, exactly like ``collect``'s contract. Raises
        TimeoutError with the admissible count otherwise.
        """
        t0 = time.monotonic()
        deadline = t0 + timeout_ms / 1000.0
        lo = round_ - max_staleness
        sp = _trace.span(
            "gather", step=int(round_), q=int(q),
            max_staleness=int(max_staleness), plane=self._plane,
        )
        with sp, self._cond:
            while True:
                adm = {
                    p: f for p, f in self._frames.items() if f[0] >= lo
                }
                if len(adm) >= q:
                    newest = max(g for _, _, g in adm.values())
                    if not require_fresh or newest > self._mark:
                        self._mark = max(self._mark, newest)
                        sp.set(
                            arrived=len(adm),
                            reused=sum(
                                1 for s, _, _ in adm.values() if s < round_
                            ),
                        )
                        _emit_wait(
                            round_, q, len(adm), time.monotonic() - t0,
                            plane=self._plane,
                        )
                        return {p: (s, pl) for p, (s, pl, _) in adm.items()}
                remaining = deadline - time.monotonic()
                if remaining <= 0 or self._ex._closing.is_set():
                    sp.set(arrived=len(adm), timed_out=True)
                    _emit_wait(
                        round_, q, len(adm), time.monotonic() - t0,
                        timed_out=True, plane=self._plane,
                    )
                    raise TimeoutError(
                        f"only {len(adm)}/{q} peers within staleness "
                        f"{max_staleness} of round {round_} after "
                        f"{timeout_ms} ms"
                    )
                self._cond.wait(timeout=min(remaining, 1.0))

    def close(self):
        for idx in list(self.peers()):
            self.remove_peer(idx)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
