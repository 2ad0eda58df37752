"""PeerExchange: host-level wait-n-f over TCP + the native MRMW register.

These are the tests that fail if MultiBuffer breaks in a way a user feels
(VERDICT r1 #8): the exchange's blocking rendezvous IS the register —
frames land via ``write``, ``collect`` wakes via ``read(min_version)``.
Three peers run in one process on localhost ports; the cross-process case
is covered by tests/test_multihost_integration.py.
"""

import socket

import pytest

pytest.importorskip("garfield_tpu.native")
from garfield_tpu import native

if native.load() is None:  # no compiler / native runtime in this env
    pytest.skip("native runtime unavailable", allow_module_level=True)

from garfield_tpu.utils.exchange import PeerExchange


def _ports(k):
    socks = [socket.socket() for _ in range(k)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def _mesh(n):
    hosts = [f"127.0.0.1:{p}" for p in _ports(n)]
    return [PeerExchange(i, hosts) for i in range(n)]


def test_all_publish_all_collect():
    peers = _mesh(3)
    try:
        for step in range(3):  # versions advance across steps
            for p in peers:
                p.publish(step, f"s{step}p{p.my_index}".encode())
            for p in peers:
                got = p.collect(step, q=3, timeout_ms=10_000)
                assert got == {
                    i: f"s{step}p{i}".encode() for i in range(3)
                }
    finally:
        for p in peers:
            p.close()


def test_wait_nf_excludes_straggler():
    peers = _mesh(3)
    try:
        # Peer 2 never publishes: the q=2 quorum must return without it.
        for p in peers[:2]:
            p.publish(0, bytes([p.my_index]))
        got = peers[0].collect(0, q=2, timeout_ms=10_000)
        assert set(got) == {0, 1}
        # ...and demanding all 3 times out (ps.py:84-88 bounded-wait exit).
        with pytest.raises(TimeoutError):
            peers[1].collect(0, q=3, timeout_ms=300)
    finally:
        for p in peers:
            p.close()


def test_overwritten_step_is_not_mixed_in():
    """Exact-step semantics: once a peer's newer frame overwrites the
    requested step in the last-writer-wins register, that peer cannot join
    the quorum with wrong-iteration data — the collect times out instead."""
    peers = _mesh(2)
    try:
        peers[0].publish(0, b"own-step0")
        peers[1].publish(0, b"peer-step0")
        peers[1].publish(1, b"peer-step1")  # overwrites step 0 in flight
        # Wait until peer 1's frames have landed in peer 0's register.
        import time

        deadline = time.time() + 10
        while peers[0]._mb.version(1) < 2 and time.time() < deadline:
            time.sleep(0.02)
        got = peers[0].collect(0, q=1, timeout_ms=5_000)
        assert got == {0: b"own-step0"}  # own slot still holds step 0
        with pytest.raises(TimeoutError):
            peers[0].collect(0, q=2, timeout_ms=300)  # step 0 gone for peer 1
    finally:
        for p in peers:
            p.close()


def test_publish_does_not_stall_on_crashed_peer():
    """ADVICE r2 (medium): once a peer has crashed, every subsequent
    publish must not burn the full first-connect grace window
    (connect_retry_ms, default 10 s) re-dialing it — reconnects get one
    short attempt and the frame is dropped (fire-and-forget contract)."""
    import time

    peers = _mesh(2)
    try:
        for p in peers:
            p.publish(0, b"warm")  # establishes both send sockets
        for p in peers:
            assert len(p.collect(0, q=2, timeout_ms=10_000)) == 2
        peers[1].close()  # peer 1 crashes
        # Publishes from peer 0 keep flowing; each must return fast even
        # though peer 1's endpoint now refuses/ignores connections.
        t0 = time.monotonic()
        for step in range(1, 4):
            peers[0].publish(step, b"alone")
        elapsed = time.monotonic() - t0
        assert elapsed < peers[0].connect_retry_ms / 1000.0, (
            f"publish stalled {elapsed:.1f}s on a crashed peer"
        )
        # Own slot still collects: the survivor makes progress at q=1.
        got = peers[0].collect(3, q=1, timeout_ms=5_000)
        assert got == {0: b"alone"}
    finally:
        for p in peers:
            p.close()


def test_read_latest_catches_up_past_overwrites():
    """read_latest: a slow consumer of a fast producer's last-writer-wins
    slot accepts the NEWEST frame >= its expected step instead of dying on
    the overwritten exact step (the cluster worker's model-plane read)."""
    import threading
    import time

    peers = _mesh(2)
    try:
        # Producer races ahead: steps 0..3 land, only 3 survives.
        for s in range(4):
            peers[1].publish(s, f"m{s}".encode())
        deadline = time.time() + 10
        while peers[0]._mb.version(1) < 4 and time.time() < deadline:
            time.sleep(0.02)
        step, payload = peers[0].read_latest(1, 1, timeout_ms=5_000)
        assert (step, payload) == (3, b"m3")
        # Expecting a FUTURE step blocks until it is published.
        result = {}

        def waiter():
            result["got"] = peers[0].read_latest(1, 7, timeout_ms=15_000)

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.2)
        peers[1].publish(7, b"m7")
        t.join(timeout=15)
        assert not t.is_alive()
        assert result["got"] == (7, b"m7")
        # And a producer that never advances times out.
        with pytest.raises(TimeoutError):
            peers[0].read_latest(1, 99, timeout_ms=200)
    finally:
        for p in peers:
            p.close()


def test_late_joiner_catches_up():
    """A collect blocked on a not-yet-published step wakes when the frame
    arrives — the blocking-read path of the register, no polling."""
    import threading
    import time

    peers = _mesh(2)
    try:
        result = {}

        def waiter():
            result.update(peers[0].collect(5, q=2, timeout_ms=15_000))

        peers[0].publish(5, b"self")
        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.2)  # let the waiter block on the register
        peers[1].publish(5, b"late")
        t.join(timeout=15)
        assert not t.is_alive()
        assert result == {0: b"self", 1: b"late"}
    finally:
        for p in peers:
            p.close()


def test_collect_begin_cancel_retires_waiters():
    """Watcher lifecycle (DESIGN.md §14 satellite): a registration a role
    never harvests must cancel its waiter threads promptly — before this
    fix they lingered until the deadline or close(), leaking one thread
    per peer per abandoned round."""
    import time

    peers = _mesh(2)
    try:
        wait = peers[0].collect_begin(50, q=2, timeout_ms=600_000)
        time.sleep(0.3)
        assert sum(t.is_alive() for t in peers[0]._waiters) == 2
        wait.cancel()
        deadline = time.time() + 5
        while (any(t.is_alive() for t in peers[0]._waiters)
               and time.time() < deadline):
            time.sleep(0.05)
        assert not any(t.is_alive() for t in peers[0]._waiters)
    finally:
        for p in peers:
            p.close()


def test_harvest_auto_cancels_pending_waiters():
    """A harvested registration releases its beyond-quorum waiters
    immediately instead of at their deadline."""
    import time

    peers = _mesh(3)
    try:
        for p in peers[:2]:
            p.publish(4, b"x")
        wait = peers[0].collect_begin(4, q=2, timeout_ms=600_000)
        got = wait()
        assert set(got) == {0, 1}
        deadline = time.time() + 5
        while (any(t.is_alive() for t in peers[0]._waiters)
               and time.time() < deadline):
            time.sleep(0.05)
        assert not any(t.is_alive() for t in peers[0]._waiters), (
            "peer 2's waiter survived the harvest"
        )
    finally:
        for p in peers:
            p.close()


def test_read_latest_begin_cancel_retires_watcher():
    import time

    peers = _mesh(2)
    try:
        wait = peers[0].read_latest_begin(1, 99)
        time.sleep(0.2)
        assert any(t.is_alive() for t in peers[0]._waiters)
        wait.cancel()
        deadline = time.time() + 5
        while (any(t.is_alive() for t in peers[0]._waiters)
               and time.time() < deadline):
            time.sleep(0.05)
        assert not any(t.is_alive() for t in peers[0]._waiters)
    finally:
        for p in peers:
            p.close()


def test_round_collector_stale_reuse_and_cutoff():
    """The bounded-staleness quorum primitive (DESIGN.md §14): admissible
    frames are reused across gathers within the cutoff; past it the
    gather times out instead of mixing over-stale data in."""
    peers = _mesh(3)
    try:
        col = peers[0].round_collector([1, 2])
        peers[1].publish(5, b"p1r5", to=[0])
        peers[2].publish(3, b"p2r3", to=[0])
        got = col.gather(5, 2, max_staleness=2, timeout_ms=10_000)
        assert got == {1: (5, b"p1r5"), 2: (3, b"p2r3")}
        # Stale REUSE: round 6 re-admits peer 2's round-3 frame (tau=3)
        # without a re-collect; peer 1's new frame is the fresh floor.
        peers[1].publish(6, b"p1r6", to=[0])
        got = col.gather(6, 2, max_staleness=3, timeout_ms=10_000)
        assert got == {1: (6, b"p1r6"), 2: (3, b"p2r3")}
        # Hard cutoff: at round 8 with max_staleness=2 the round-3 frame
        # is inadmissible — 1/2 peers only.
        peers[1].publish(8, b"p1r8", to=[0])
        with pytest.raises(TimeoutError, match="1/2"):
            col.gather(8, 2, max_staleness=2, timeout_ms=300)
        col.close()
    finally:
        for p in peers:
            p.close()


def test_round_collector_freshness_membership_transform():
    """One mesh (the close() tax dominates this file's runtime), three
    contracts: the freshness floor (a gather must include >= 1 NEW
    arrival — no free-running on cached frames), membership changes
    (remove_peer retires the watcher + frame, add_peer restarts — the
    churn leave/join path), and the transform-error ban-evidence storage
    (same contract as collect())."""
    import threading
    import time

    peers = _mesh(3)
    try:
        # --- freshness floor (collector over peer 1 only) -------------
        col = peers[0].round_collector([1])
        peers[1].publish(1, b"r1", to=[0])
        assert col.gather(1, 1, max_staleness=4, timeout_ms=10_000) == {
            1: (1, b"r1")
        }
        result = {}

        def g():
            result.update(col.gather(2, 1, max_staleness=4,
                                     timeout_ms=15_000))

        t = threading.Thread(target=g)
        t.start()
        time.sleep(0.4)
        assert not result, "gather returned without a fresh arrival"
        peers[1].publish(2, b"r2", to=[0])
        t.join(timeout=10)
        assert result == {1: (2, b"r2")}
        # require_fresh=False reuses freely.
        assert col.gather(3, 1, max_staleness=4, timeout_ms=10_000,
                          require_fresh=False) == {1: (2, b"r2")}

        # --- membership (second collector, peers 1+2) ------------------
        col2 = peers[0].round_collector([1, 2])
        peers[2].publish(2, b"b", to=[0])
        col2.gather(2, 2, max_staleness=0, timeout_ms=10_000)
        col2.remove_peer(2)
        assert col2.peers() == [1]
        peers[1].publish(3, b"a3", to=[0])
        assert col2.gather(3, 1, max_staleness=0, timeout_ms=10_000) == {
            1: (3, b"a3")
        }
        col2.add_peer(2)
        peers[2].publish(3, b"b3", to=[0])
        got = col2.gather(3, 2, max_staleness=0, timeout_ms=10_000,
                          require_fresh=False)
        assert got == {1: (3, b"a3"), 2: (3, b"b3")}

        # --- transform error stored as ban evidence --------------------
        def boom(idx, payload):
            raise ValueError(f"bad frame from {idx}")

        col3 = peers[0].round_collector([2], transform=boom)
        peers[2].publish(4, b"x", to=[0])
        tag, payload = col3.gather(
            4, 1, max_staleness=0, timeout_ms=10_000
        )[2]
        assert tag == 4 and isinstance(payload, ValueError)

        # --- close() retires every watcher -----------------------------
        for c in (col, col2, col3):
            c.close()
            assert c.peers() == []
        deadline = time.time() + 5
        while (any(t.is_alive() for t in peers[0]._waiters)
               and time.time() < deadline):
            time.sleep(0.05)
        assert not any(t.is_alive() for t in peers[0]._waiters)
    finally:
        for p in peers:
            p.close()


def test_collect_begin_latches_before_overwrite():
    """Pre-registered waiters (collect_begin) must latch a frame that is
    later overwritten — the publish-then-collect race a symmetric gossip
    protocol hits on an oversubscribed host (apps/cluster._run_learn)."""
    import threading

    peers = _mesh(2)
    latched = {0: threading.Event(), 1: threading.Event()}

    def note(idx, payload):  # runs on the waiter thread as the frame lands
        latched[idx].set()
        return payload

    try:
        wait = peers[0].collect_begin(7, q=2, timeout_ms=15_000,
                                      transform=note)
        peers[1].publish(7, b"frame7")
        assert latched[1].wait(15)  # latched by the registered reader...
        peers[1].publish(8, b"frame8")  # ...then overwritten in the slot
        peers[0].publish(7, b"self")
        got = wait()
        assert got == {0: b"self", 1: b"frame7"}

        # Control: a collect REGISTERED after the overwrite (frame 8 is in
        # the slot) cannot see 7.
        assert peers[0].read_latest(1, 8, timeout_ms=15_000) == (
            8, b"frame8")
        with pytest.raises(TimeoutError):
            peers[0].collect(7, q=1, peers=[1], timeout_ms=300)
    finally:
        for p in peers:
            p.close()


def _mesh_planes(n, planes):
    hosts = [f"127.0.0.1:{p}" for p in _ports(n)]
    return [PeerExchange(i, hosts, planes=planes) for i in range(n)]


def test_per_plane_slots_do_not_overwrite_each_other():
    """DESIGN.md §15: each (peer, plane) has its OWN register slot, so a
    multi-plane protocol (LEARN async gossip) publishing gradients and
    models for the same round no longer loses one plane's frame to the
    other's last-writer-wins overwrite — the multiplexing limitation the
    per-plane refactor removes."""
    peers = _mesh_planes(2, 3)
    try:
        # Same ROUND TAG on every plane: before per-plane slots, these
        # three publishes would overwrite one register cell.
        peers[1].publish(5, b"grad", plane=1)
        peers[1].publish(5, b"model", plane=2)
        peers[1].publish(5, b"ctrl", plane=0)
        assert peers[0].collect(
            5, q=1, peers=[1], plane=1, timeout_ms=10_000
        ) == {1: b"grad"}
        assert peers[0].collect(
            5, q=1, peers=[1], plane=2, timeout_ms=10_000
        ) == {1: b"model"}
        assert peers[0].collect(
            5, q=1, peers=[1], plane=0, timeout_ms=10_000
        ) == {1: b"ctrl"}
        # read_latest is plane-scoped too.
        step, payload = peers[0].read_latest(1, 5, plane=2)
        assert (step, payload) == (5, b"model")
    finally:
        for p in peers:
            p.close()


def test_plane_out_of_range_rejected():
    """ISSUE 13 satellite (boundary): the plane/shard tag rides spare
    header bits, so EVERY plane-taking entry point must fail loudly at
    the exact capacity boundary — a silently truncated tag would
    deliver one shard's frames into another shard's fold."""
    peers = _mesh_planes(2, 2)
    try:
        # In-range boundary works...
        peers[0].publish(1, b"ok", plane=1)
        # ...one past it fails on every entry point, loudly.
        with pytest.raises(ValueError):
            peers[0].publish(1, b"x", plane=2)
        with pytest.raises(ValueError):
            peers[0].round_collector([1], plane=5)
        with pytest.raises(ValueError):
            peers[0].collect_begin(1, q=1, peers=[1], plane=2)
        with pytest.raises(ValueError):
            peers[0].read_latest_begin(1, 0, plane=2)
        with pytest.raises(ValueError):
            peers[0].read_latest(1, 0, plane=2, timeout_ms=10)
        with pytest.raises(ValueError):
            peers[0].publish(1, b"x", plane=-1)
        # Non-integral tags are rejected, not int()-truncated.
        with pytest.raises(TypeError):
            peers[0].publish(1, b"x", plane=1.5)
    finally:
        for p in peers:
            p.close()
    with pytest.raises(ValueError):
        PeerExchange(0, ["127.0.0.1:1"], planes=0)
    # The exchange's plane space is capped at the wire header nibble's
    # 16 values — planes=17 must be refused at construction.
    with pytest.raises(ValueError):
        PeerExchange(0, ["127.0.0.1:1"], planes=17)


def test_round_collectors_per_plane_independent():
    """One collector per plane over the SAME peers: each gathers its own
    plane's frames, and newest() reads that plane's swarm clock."""
    peers = _mesh_planes(2, 3)
    try:
        cg = peers[0].round_collector([1], plane=1)
        cm = peers[0].round_collector([1], plane=2)
        peers[1].publish(3, b"g3", plane=1)
        peers[1].publish(2, b"m2", plane=2)
        got_g = cg.gather(3, 1, timeout_ms=10_000)
        got_m = cm.gather(2, 1, timeout_ms=10_000)
        assert got_g == {1: (3, b"g3")}
        assert got_m == {1: (2, b"m2")}
        assert cg.newest() == 3 and cm.newest() == 2
        cg.close()
        cm.close()
    finally:
        for p in peers:
            p.close()


def test_remove_peer_tears_down_all_watchers():
    """Regression (ISSUE 9 satellite): a churn leave used to cancel the
    round collector's watcher for the departed peer but LEAK any
    read_latest_begin latch (and leave collect waiters to their
    deadline). exchange.remove_peer now retires collect waiters,
    read_latest latches AND collector watchers on that peer
    symmetrically — and only that peer's."""
    import time

    peers = _mesh(3)
    try:
        ex = peers[0]
        # One of each watcher kind on peer 1, plus controls on peer 2.
        latch = ex.read_latest_begin(1, 99)
        wait = ex.collect_begin(42, q=2, peers=[1, 2], timeout_ms=600_000)
        col = ex.round_collector([1, 2])
        time.sleep(0.3)
        alive0 = sum(t.is_alive() for t in ex._waiters)
        assert alive0 >= 5  # latch + 2 collect waiters + 2 col watchers

        ex.remove_peer(1)
        deadline = time.time() + 5
        while (sum(t.is_alive() for t in ex._waiters) > 2
               and time.time() < deadline):
            time.sleep(0.05)
        # Exactly peer 2's collect waiter + collector watcher survive.
        assert sum(t.is_alive() for t in ex._waiters) == 2
        assert col.peers() == [2]

        # The collector still gathers from the survivor; the removed
        # peer's frames cannot resurrect.
        peers[2].publish(7, b"ok")
        assert col.gather(7, 1, timeout_ms=10_000) == {2: (7, b"ok")}
        wait.cancel()
        latch.cancel()
        col.close()
    finally:
        for p in peers:
            p.close()


# ---------------------------------------------------------------------------
# harvest-time batch transform (ISSUE 20)


def test_batch_transform_harvests_quorum_in_one_call():
    """batch_transform sees the whole quorum's latched raw frames as
    sorted (peer, payload) items in ONE call at harvest time and must
    return one result per item; results map back to peers."""
    peers = _mesh(4)
    calls = []

    def batch(items):
        calls.append([i for i, _ in items])
        return [payload.decode() + "!" for _, payload in items]

    try:
        wait = peers[0].collect_begin(
            0, q=3, peers=[1, 2, 3], timeout_ms=10_000,
            batch_transform=batch,
        )
        for p in peers[1:]:
            p.publish(0, f"p{p.my_index}".encode(), to=[0])
        got = wait()
    finally:
        for p in peers:
            p.close()
    assert len(calls) == 1 and calls[0] == sorted(calls[0])
    assert got == {i: f"p{i}!" for i in calls[0]}


def test_batch_transform_exception_results_and_hook_failure():
    """Step 0: an exception INSTANCE returned for one item is stored for
    that peer only (the per-frame transform's stored-exception
    convention, batched). Step 1: the whole hook raising stores the
    exception for EVERY item. One mesh, two rounds — the close cost of
    a localhost mesh dominates these tests."""
    peers = _mesh(3)

    def batch_instance(items):
        return [
            ValueError(f"bad {i}") if i == 2 else len(p)
            for i, p in items
        ]

    def batch_raise(items):
        raise RuntimeError("decoder exploded")

    try:
        wait = peers[0].collect_begin(
            0, q=2, peers=[1, 2], timeout_ms=10_000,
            batch_transform=batch_instance,
        )
        peers[1].publish(0, b"fine", to=[0])
        peers[2].publish(0, b"forged", to=[0])
        got = wait()
        assert got[1] == 4
        assert isinstance(got[2], ValueError) and "bad 2" in str(got[2])

        wait = peers[0].collect_begin(
            1, q=2, peers=[1, 2], timeout_ms=10_000,
            batch_transform=batch_raise,
        )
        for p in peers[1:]:
            p.publish(1, b"x", to=[0])
        got = wait()
        assert set(got) == {1, 2}
        assert all(isinstance(v, RuntimeError) for v in got.values())
    finally:
        for p in peers:
            p.close()


def test_batch_transform_exclusivity_and_length_mismatch():
    peers = _mesh(2)
    try:
        with pytest.raises(ValueError, match="batch_transform"):
            peers[0].collect_begin(
                0, q=1, peers=[1], transform=lambda i, p: p,
                batch_transform=lambda items: [p for _, p in items],
            )
        wait = peers[0].collect_begin(
            0, q=1, peers=[1], timeout_ms=10_000,
            batch_transform=lambda items: [],
        )
        peers[1].publish(0, b"x", to=[0])
        with pytest.raises(RuntimeError, match="batch_transform"):
            wait()
    finally:
        for p in peers:
            p.close()
