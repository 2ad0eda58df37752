"""Federated round bench: million-client sharded rounds (FEDBENCH_r*).

Three checks, one committed artifact (schema v10 ``fed_bench`` rows):

``scaling``
    The headline: n = 10^6 sampled clients per round, end to end
    (cohort sample -> wave ingest -> per-shard hier-GAR fold -> shard
    broadcast encode) at S in {1, 2, 4} shards. Each (cell, shard) runs
    in its OWN OS process (``--shard_run`` child) — the deployment
    shape, and the only honest way to record per-shard-process RSS.
    The container is 1-core, so shard processes run back to back and a
    cell's ``round_s`` is the MAX over its shard processes' per-round
    walls — the round time of the real deployment, where the S shards
    are independent processes on S cores with no cross-shard traffic;
    ``round_s_sum`` records the serialized total so the 1-core
    provenance is never hidden. Gradients are simulated from two cycled
    pools (generation outside the timed region);
    every shard slices the SAME pool bytes, so cells differ only in
    shard width.

``s1_bitwise``
    The anchor: the engine at S=1 with full participation runs a
    multi-round trajectory bitwise equal to the existing unsharded
    single-PS streaming path (StreamingAggregator + the same
    ``model -= lr * agg`` update) — sharding is a strict generalization,
    not a fork.

``fleet``
    The elastic half: a REAL client fleet (jax-free ``--client``
    subprocesses over PeerExchange, one wire frame per shard per round,
    shard-stamped) driven by ``federated.ClientFleet`` /
    ``utils.autoscale``. The round's fixed cohort is partitioned across
    the active drivers, each sleeping a per-client compute delay, so
    spawning drivers genuinely parallelizes the round (sleeps overlap
    even on one core): the controller starts under-provisioned, spawns
    toward ``--fleet_target`` rounds/s, and the row records
    pre/recovered rates + membership actions.

  python -m garfield_tpu.apps.benchmarks.fed_bench --json FEDBENCH.json
"""

import argparse
import json
import os
import subprocess
import socket
import sys
import time

import numpy as np

from ...utils import wire
from ...utils.exchange import PeerExchange

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))
)))

# Fleet stop sentinel: a round tag no real round reaches.
_STOP_ROUND = 2 ** 40


def _rss():
    import resource

    return int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss) * 1024


def _ports(k):
    socks = [socket.socket() for _ in range(k)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def _spawn_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        _REPO + os.pathsep + env["PYTHONPATH"]
        if env.get("PYTHONPATH") else _REPO
    )
    env["JAX_PLATFORMS"] = "cpu"
    return env


# --- the shard child (one PS shard process of one scaling cell) -------------


# Span-name -> artifact phase-name map for the child's per-phase digest
# (schema v12): the trace plane's hierarchy spans keep their producer
# names in the JSONL stream; the fed_bench row speaks the ISSUE's
# vocabulary (ingest/h2d/fold).
_PHASE_NAMES = {
    "hier_ingest": "ingest",     # pre-timed, one per dispatched wave —
    #                              counts align 1:1 with h2d/fold (the
    #                              v12 capture timed an OUTER push_rows
    #                              span instead and undercounted)
    "hier_h2d": "h2d",           # staging one wave onto the device
    "hier_wave": "fold",         # wave dispatch (+ readback in sync mode)
    "hier_fold_wait": "fold_wait",  # double-buffer blocking readback
    "hier_finalize": "finalize",
}


def _shard_run(args):
    """One shard process of one scaling cell: sample the cohort, ingest
    its own column span of every cohort row, fold, encode the broadcast
    frame. Prints one JSON line the parent aggregates. The first round
    is a warmup (fold-program compiles) and is not reported. The child
    installs a private MetricsHub + trace for the timed rounds, so the
    line carries per-phase p50/p95 (schema v12): ingest waves, H2D
    staging and wave fold dispatch/readback."""
    from ... import federated as fed
    from ...telemetry import hub as tele_hub
    from ...telemetry import trace as tele_trace

    spec = fed.plan_shards(args.d, args.shards)
    s = args.shard_index
    sampler = fed.CohortSampler(
        args.population, args.n, seed=args.seed, byz_frac=args.byz_frac,
        bucket_gar=args.bucket_gar,
    )
    f = sampler.f_budget()
    server = fed.ShardServer(s, spec, bucket_gar=args.bucket_gar,
                             wave_buckets=args.wave)
    rng = np.random.default_rng(args.seed)
    wave_rows = args.wave * 32
    pools = [rng.normal(size=(wave_rows, args.d)).astype(np.float32)
             for _ in range(2)]
    hub = tele_hub.MetricsHub()
    walls, bytes_out = [], 0
    for r in range(args.rounds + 1):  # +1: round 0 is compile warmup
        if r == 1:
            # Arm the phase digest AFTER the warmup round: round 0's
            # compile-dominated spans would pollute the tails the
            # artifact commits.
            tele_hub.install(hub)
            tele_trace.enable(who=f"fed-shard-{s}")
        t0 = time.perf_counter()
        cohort = sampler.cohort(r)
        server.begin_round(r, cohort.size, f)
        i = 0
        while i < cohort.size:
            pool = pools[(i // wave_rows) % 2]
            take = min(wave_rows, cohort.size - i)
            # Ingest attribution rides the reducer's per-wave
            # hier_ingest spans (trace.emit) — no outer span here, so
            # counts align with hier_wave/hier_h2d instead of one span
            # per push_rows call. stable=True: the pool slice is a
            # C-contiguous f32 block untouched until the next wave's
            # readback, so whole waves fold zero-copy.
            server.push_rows(spec.slice_rows(pool[:take], s), stable=True)
            i += take
        agg = server.finish_round()
        frame = wire.encode(agg, plane=s)  # the shard broadcast payload
        bytes_out = len(frame)
        if r > 0:
            walls.append(time.perf_counter() - t0)
    phases = {
        _PHASE_NAMES.get(ph, ph): {
            "count": int(st["count"]),
            "p50_s": round(st["p50_s"], 9),
            "p95_s": round(st["p95_s"], 9),
        }
        for ph, st in (hub.phase_stats() or {}).items()
    }
    print(json.dumps({
        "shard": s, "walls": [round(w, 4) for w in walls],
        "f_budget": f, "d_shard": spec.width(s),
        "broadcast_bytes": bytes_out, "peak_rss_bytes": _rss(),
        "phases": phases or None,
    }), flush=True)


def _spawn_shard(args, gar, shards, shard_index):
    return subprocess.Popen(
        [sys.executable, "-m", "garfield_tpu.apps.benchmarks.fed_bench",
         "--shard_run", "--shards", str(shards),
         "--shard_index", str(shard_index),
         "--n", str(args.n), "--population", str(args.population),
         "--d", str(args.d), "--rounds", str(args.rounds),
         "--seed", str(args.seed), "--byz_frac", str(args.byz_frac),
         "--bucket_gar", gar, "--wave", str(args.wave)],
        env=_spawn_env(), stdout=subprocess.PIPE, text=True,
    )


def scaling_cell(args, gar, shards):
    """One scaling cell: S shard processes, run back to back (1-core
    container — see the module docstring), round_s = max over shards of
    the per-shard min-over-rounds wall."""
    reports = []
    for s in range(shards):
        p = _spawn_shard(args, gar, shards, s)
        out, _ = p.communicate(timeout=3600)
        if p.returncode != 0:
            raise RuntimeError(f"shard {s}/{shards} failed:\n{out[-2000:]}")
        reports.append(json.loads(out.strip().splitlines()[-1]))
    per_shard_s = [min(r["walls"]) for r in reports]
    round_s = max(per_shard_s)
    # The row's per-phase attribution (schema v12) is the BOTTLENECK
    # shard's digest — the shard whose wall defines round_s is the one
    # whose phase breakdown explains it.
    phases = reports[per_shard_s.index(round_s)].get("phases")
    return {
        **({"phases": phases} if phases else {}),
        "check": "scaling", "n": args.n, "population": args.population,
        "d": args.d, "shards": shards, "gar": f"hier-{gar}",
        "f": reports[0]["f_budget"], "rounds": args.rounds,
        "round_s": round(round_s, 4),
        "round_s_sum": round(sum(per_shard_s), 4),
        "per_client_s": round(round_s / args.n, 9),
        "per_shard_s": [round(x, 4) for x in per_shard_s],
        "per_shard_rss": [r["peak_rss_bytes"] for r in reports],
        "peak_rss_bytes": max(r["peak_rss_bytes"] for r in reports),
        "shards_serialized_on_host": True,
        "wave_buckets": args.wave,
    }


# --- the S=1 bitwise anchor --------------------------------------------------


def bitwise_cell(args):
    """S=1 full participation over several rounds, bitwise vs the
    unsharded single-PS streaming path (the pre-sharding SSMW shape:
    one StreamingAggregator over the full vector + the same SGD
    update)."""
    from ... import federated as fed
    from ...aggregators import hierarchy

    n, d, rounds = args.bitwise_n, args.bitwise_d, 3
    rng = np.random.default_rng(args.seed)
    model0 = rng.normal(size=d).astype(np.float32)
    sampler = fed.CohortSampler(n, n, seed=args.seed,
                                byz_frac=args.byz_frac,
                                bucket_gar=args.bucket_gar)
    eng = fed.FedRoundEngine(model0, 1, sampler, lr=0.05,
                             bucket_gar=args.bucket_gar,
                             wave_buckets=args.wave)
    ref = model0.copy()
    t0 = time.perf_counter()
    for r in range(rounds):
        ids, f = eng.begin_round()
        g = np.random.default_rng([args.seed, 7, r]).normal(
            size=(ids.size, d)).astype(np.float32)
        eng.ingest_rows(g)
        eng.finish_round()
        red = hierarchy.StreamingAggregator(
            ids.size, f, bucket_gar=args.bucket_gar,
            wave_buckets=args.wave,
        )
        red.push_many(g)
        ref = (ref - np.float32(0.05) * red.finalize()).astype(np.float32)
    equal = bool(np.array_equal(eng.model, ref))
    return {
        "check": "s1_bitwise", "n": n, "population": n, "d": d,
        "shards": 1, "gar": f"hier-{args.bucket_gar}", "rounds": rounds,
        "s1_bitwise_equal": equal,
        "round_s": round((time.perf_counter() - t0) / (2 * rounds), 4),
        "peak_rss_bytes": _rss(),
    }


# --- the ingest micro-mode (batch vs per-frame decode) -----------------------


def ingest_micro_cell(args):
    """Batch-vs-per-frame decode isolation (INGESTBENCH_r*): for every
    frame width x wire scheme x batch size, encode ``batch`` frames of
    ``d`` elems, then decode them (a) per frame through ``decode_into``
    — the pre-ISSUE-20 ingest loop — and (b) in one
    ``decode_batch_into`` call into the same slab. Both paths are
    asserted bitwise-identical before any timing is committed, and
    min-over-reps is recorded (the usual micro-timing discipline: the
    floor is the signal on a noisy shared host). The ``--ingest_d``
    sweep brackets the claim: at small frames the per-frame Python
    header trip dominates and the vectorized screen wins; at the
    scaling cells' d_shard the CRC+memcpy floor dominates BOTH paths
    and batch is a wash — committed either way (DESIGN.md §24). A
    final pair of f32 rows per width times the CRC thread pool
    (``GARFIELD_INGEST_THREADS=2``) against inline CRC at the largest
    batch — on this 1-core container that is the §24 negative result,
    committed rather than hidden. Rows are schema-v15 ``fed_bench``
    records (check="ingest_micro"): the decode micro has no GAR in the
    loop, so ``gar`` is the literal "none" and the n/shards envelope
    describes the batch itself."""
    reps = args.ingest_reps
    rng = np.random.default_rng(args.seed)
    rows = []

    def _time(fn):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    def _row(d, scheme, batch, frames, *, threads=0):
        out_seq = np.empty((batch, d), np.float32)
        out_bat = np.empty((batch, d), np.float32)
        os.environ["GARFIELD_INGEST_THREADS"] = str(threads)
        try:
            def per_frame():
                for i, fr in enumerate(frames):
                    wire.decode_into(fr, out_seq[i], expect_elems=d)

            def batched():
                res = wire.decode_batch_into(frames, out_bat,
                                             expect_elems=d)
                assert all(r == d for r in res), res

            per_frame()
            batched()
            equal = bool(np.array_equal(out_seq, out_bat))
            assert equal, f"batch decode diverged: {scheme} k={batch}"
            per_s, bat_s = _time(per_frame), _time(batched)
        finally:
            os.environ.pop("GARFIELD_INGEST_THREADS", None)
        return {
            "check": "ingest_micro", "n": batch, "d": d, "shards": 1,
            "gar": "none", "scheme": scheme, "batch": batch,
            "threads": threads, "frame_bytes": len(frames[0]),
            "per_frame_s": round(per_s, 9), "batch_s": round(bat_s, 9),
            "speedup": round(per_s / bat_s, 3),
            "bitwise_equal": equal, "reps": reps,
            "peak_rss_bytes": _rss(),
        }

    for d in args.ingest_d:
        for scheme in wire.WIRE_SCHEMES:
            for batch in args.ingest_batches:
                vecs = rng.normal(size=(batch, d)).astype(np.float32)
                frames = [wire.encode(vecs[i], scheme, plane=1)
                          for i in range(batch)]
                row = _row(d, scheme, batch, frames)
                rows.append(row)
                print(f"ingest_micro d={d} {scheme} k={batch}: "
                      f"per_frame={row['per_frame_s'] * 1e3:.3f}ms "
                      f"batch={row['batch_s'] * 1e3:.3f}ms "
                      f"speedup={row['speedup']}", flush=True)
        # The thread-pool A/B at the largest f32 batch: same frames,
        # pool on vs off — committed either way (negative result on
        # 1 core).
        batch = max(args.ingest_batches)
        vecs = rng.normal(size=(batch, d)).astype(np.float32)
        frames = [wire.encode(vecs[i], "f32", plane=1)
                  for i in range(batch)]
        for threads in (0, 2):
            row = _row(d, "f32", batch, frames, threads=threads)
            rows.append(row)
            print(f"ingest_micro d={d} f32 k={batch} threads={threads}: "
                  f"batch={row['batch_s'] * 1e3:.3f}ms", flush=True)
    return rows


# --- the client fleet (jax-free --client children) ---------------------------


def _client_main(args):
    """A simulated client DRIVER: follows the PS's round beacon, takes
    its block of the round's cohort, sleeps the per-client compute
    delay, and publishes one shard-stamped wave frame per shard.
    Deliberately jax-free (numpy + wire + exchange only)."""
    hosts = args.hosts.split(",")
    me = args.client_index
    ex = PeerExchange(me, hosts, connect_retry_ms=120_000,
                      planes=args.shards)
    rng = np.random.default_rng(1000 + me)
    spans = None
    dbg = os.environ.get("GARFIELD_FED_DEBUG")

    def _log(msg):
        if dbg:
            print(f"[client {me}] {msg}", file=sys.stderr, flush=True)
    try:
        ex.publish(0, b"up", to=[0], plane=0)
        last = 0
        cached = None  # (step, [(plane, frame)]): the last response
        quiet = 0
        while True:
            try:
                step, beacon = ex.read_latest(0, last + 1,
                                              timeout_ms=5_000, plane=0)
            except TimeoutError:
                # Quiet period: either the PS is gone (bail after 36
                # strikes = 3 min) or a frame was lost in EITHER
                # direction — re-publish the cached response (the PS's
                # retry republishes the beacon for the other case), so
                # a single lost frame never wedges the exact-step
                # rendezvous.
                quiet += 1
                _log(f"quiet {quiet} (last={last})")
                if quiet > 36:
                    return
                if cached is not None:
                    for s, fr in cached[1]:
                        ex.publish(cached[0], fr, to=[0], plane=s)
                continue
            quiet = 0
            if step >= _STOP_ROUND:
                return
            head = wire.decode(beacon, expect_plane=0)
            cohort, d = int(head[0]), int(head[1])
            actives = [int(x) for x in head[2:]]
            if me in actives:
                a = actives.index(me)
                base, rem = divmod(cohort, len(actives))
                k = base + (1 if a < rem else 0)
                if spans is None or spans[0] != d:
                    from ...federated.sharding import ShardSpec

                    spans = (d, ShardSpec(d, args.shards))
                if k:
                    if args.client_delay_ms:
                        time.sleep(k * args.client_delay_ms / 1e3)
                    rows = rng.normal(size=(k, d)).astype(np.float32)
                    frames = [
                        (s, wire.encode(
                            spans[1].slice_rows(rows, s).ravel(),
                            plane=s,
                        ))
                        for s in range(args.shards)
                    ]
                    for s, fr in frames:
                        ex.publish(step, fr, to=[0], plane=s)
                    cached = (step, frames)
                    _log(f"responded step {step} k={k}")
                else:
                    _log(f"step {step}: not my round (k=0)")
            else:
                _log(f"step {step}: not in actives {actives}")
            last = step
    finally:
        ex.close()


def fleet_cell(args):
    """The autoscaled fleet scenario (see the module docstring)."""
    from ... import federated as fed
    from ...telemetry import hub as tele_hub
    from ...utils import autoscale as autoscale_lib
    from .. import cluster as cluster_app

    # The fed PS is a cluster-style role process: with --telemetry it
    # reuses the per-role telemetry plane verbatim (one MetricsHub
    # streaming fed-ps.telemetry.jsonl — cluster.telemetry_open), so
    # the fleet's autoscale events, exchange waits and the v10
    # fed_round stream land in the same format as every other role.
    args.gar = f"hier-{args.bucket_gar}"
    args.fw = 0
    hub, exp = cluster_app.telemetry_open(args, "fed-ps")

    shards, d, cohort = args.fleet_shards, args.fleet_d, args.fleet_cohort
    pool_max = args.fleet_max
    ports = _ports(1 + pool_max)
    hosts = [f"127.0.0.1:{p}" for p in ports]
    spec = fed.plan_shards(d, shards)
    sampler = fed.CohortSampler(
        max(4 * cohort, cohort), cohort, seed=args.seed,
        byz_frac=args.byz_frac, bucket_gar=args.bucket_gar,
    )
    f = sampler.f_budget()
    servers = [
        fed.ShardServer(s, spec, bucket_gar=args.bucket_gar,
                        wave_buckets=args.wave)
        for s in range(shards)
    ]
    ex = PeerExchange(0, hosts, connect_retry_ms=120_000, planes=shards)

    def command_for(k):
        return [
            sys.executable, "-m",
            "garfield_tpu.apps.benchmarks.fed_bench",
            "--client", "--client_index", str(1 + k),
            "--hosts", ",".join(hosts), "--shards", str(shards),
            "--client_delay_ms", str(args.fleet_delay_ms),
        ]

    cfg = autoscale_lib.AutoscaleConfig(
        target_rate=args.fleet_target, min_workers=1,
        max_workers=pool_max, window=5, cooldown=3,
    )
    fleet = fed.ClientFleet(command_for, cfg, env=_spawn_env())
    ready = set()
    rates, spawns = [], 0
    pre_rate = None
    target_bumped = args.fleet_target > 0
    try:
        fleet.spawn_initial(args.fleet_initial)
        step = 1
        t_cell = time.perf_counter()
        for r in range(args.fleet_rounds):
            for k in sorted(set(fleet.active()) - ready):
                try:
                    ex.read_latest(1 + k, 0, timeout_ms=(
                        60_000 if not ready or r == 0 else 50
                    ), plane=0)
                    ready.add(k)
                except TimeoutError:
                    pass
            actives = sorted(ready & set(fleet.active()))
            if not actives:
                time.sleep(0.2)
                continue
            t0 = time.perf_counter()
            beacon_frame = wire.encode(np.asarray(
                [cohort, d] + [1 + a for a in actives], np.float32
            ), plane=0)
            peer_idx = [1 + a for a in actives]
            got_all = None
            for attempt in range(3):
                for sv in servers:
                    sv.begin_round(step, cohort, f)
                waits = [
                    ex.collect_begin(
                        step, len(actives), peers=peer_idx,
                        timeout_ms=30_000, transform=sv.wire_transform,
                        plane=sv.shard,
                    )
                    for sv in servers
                ]
                # Beacon only to live drivers (a reserve slot's sender
                # thread would burn its connect grace every round).
                ex.publish(step, beacon_frame, to=peer_idx, plane=0)
                try:
                    got_all = [w() for w in waits]
                    break
                except TimeoutError:
                    # Lost frame somewhere: re-arm and republish — the
                    # clients' quiet-period republish covers the other
                    # direction (same shape as the cluster PS's
                    # quorum_retry).
                    for w in waits:
                        w.cancel()
                    if attempt == 2:
                        raise
                    time.sleep(0.2)  # let cancelled waiters drain
            for got in got_all:
                assert not any(
                    isinstance(v, Exception) for v in got.values()
                ), f"codec reject in fleet round {step}: {got}"
            parts = [sv.finish_round() for sv in servers]
            fed.reassemble(spec, parts)  # the round's broadcast model
            round_s = time.perf_counter() - t0
            tele_hub.emit_event(
                "fed_round", step=int(step), shards=int(shards),
                cohort=int(cohort), f_budget=int(f),
                round_s=round(round_s, 6),
                per_shard={
                    str(sv.shard): {
                        "latency_s": None,
                        "wire_bytes": int(sv.wire_bytes_in),
                    }
                    for sv in servers
                },
            )
            if r < args.fleet_warmup:
                # TCP slow start + register warmup pollute the first
                # rounds; the controller must calibrate on the initial
                # fleet's steady state, not the transient.
                step += 1
                continue
            rates.append(1.0 / round_s)
            action, moved = fleet.observe(round_s, quorum_margin=0)
            if action > 0:
                spawns += 1
            elif action < 0 and moved is not None:
                # The driver is gone NOW (retire joins the process);
                # its hello must not keep it in the next quorum.
                ready.discard(moved)
            if pre_rate is None and len(rates) >= cfg.window:
                pre_rate = len(rates[:cfg.window]) / sum(
                    1.0 / x for x in rates[:cfg.window]
                )
            if not target_bumped and fleet.controller.target > 0:
                # --fleet_target 0: the controller auto-calibrated to
                # the INITIAL fleet's measured rate; the scenario's load
                # target is 1.6x that — reachable with more drivers
                # (sleeps overlap), unreachable at the initial count, so
                # the controller must provision.
                fleet.controller.target *= 1.6
                target_bumped = True
            step += 1
        wall = time.perf_counter() - t_cell
        tail = rates[-cfg.window:]
        recovered = len(tail) / sum(1.0 / x for x in tail)
        return {
            "check": "fleet", "n": cohort, "d": d, "shards": shards,
            "gar": f"hier-{args.bucket_gar}", "f": f,
            "rounds": len(rates),
            "target_rate": round(float(fleet.controller.target), 3),
            "pre_rate": None if pre_rate is None else round(pre_rate, 3),
            "recovered_rate": round(recovered, 3),
            "achieved_rate": round(recovered, 3),
            "active_initial": args.fleet_initial,
            "active_final": len(fleet.active()),
            "spawns": max(0, fleet.spawns - args.fleet_initial),
            "retires": fleet.retires,
            "round_s": round(1.0 / recovered, 4),
            "round_s_sum": round(wall, 3),
            "peak_rss_bytes": _rss(),
        }
    finally:
        try:
            ex.publish(_STOP_ROUND, wire.encode(
                np.zeros(2, np.float32), plane=0), plane=0)
        except Exception:  # noqa: BLE001
            pass
        fleet.stop_all()
        ex.close()
        cluster_app.telemetry_close(hub, exp)


# --- entry -------------------------------------------------------------------


def main(argv=None):
    p = argparse.ArgumentParser(
        description="Federated sharded-round benchmark (FEDBENCH_r*)"
    )
    p.add_argument("--n", type=int, default=10 ** 6,
                   help="Sampled cohort size per round (the headline "
                        "n=10^6).")
    p.add_argument("--population", type=int, default=2 * 10 ** 6,
                   help="Client population the cohort samples from.")
    p.add_argument("--d", type=int, default=10 ** 4,
                   help="Model dimension (full width; shard s ingests "
                        "d/S).")
    p.add_argument("--shards_list", nargs="*", type=int, default=[1, 2, 4],
                   help="Shard counts for the scaling cells.")
    p.add_argument("--rounds", type=int, default=2,
                   help="Timed rounds per shard process (min is "
                        "committed; round 0 is compile warmup).")
    p.add_argument("--seed", type=int, default=20260805)
    p.add_argument("--byz_frac", type=float, default=0.01,
                   help="Byzantine population fraction the cohort "
                        "budget prices (sampler.f_budget).")
    p.add_argument("--bucket_gar", type=str, default="krum",
                   help="Bucket rule for the bitwise/fleet cells (and "
                        "the --shard_run child).")
    p.add_argument("--scaling_gars", nargs="*", type=str,
                   default=["median", "krum"],
                   help="Bucket rules swept by the scaling cells "
                        "(median's sortnet fold has no d-independent "
                        "selection cost, so it carries the clean 1/S "
                        "curve; krum is the recorded comparison).")
    p.add_argument("--wave", type=int, default=8)
    p.add_argument("--bitwise_n", type=int, default=2048)
    p.add_argument("--bitwise_d", type=int, default=10 ** 4)
    p.add_argument("--skip_scaling", action="store_true")
    p.add_argument("--skip_bitwise", action="store_true")
    p.add_argument("--skip_fleet", action="store_true")
    # fleet scenario knobs
    p.add_argument("--fleet_shards", type=int, default=2)
    p.add_argument("--fleet_d", type=int, default=10 ** 4)
    p.add_argument("--fleet_cohort", type=int, default=64)
    p.add_argument("--fleet_initial", type=int, default=2)
    p.add_argument("--fleet_max", type=int, default=5)
    p.add_argument("--fleet_rounds", type=int, default=50)
    p.add_argument("--fleet_warmup", type=int, default=4)
    p.add_argument("--fleet_delay_ms", type=float, default=8.0,
                   help="Simulated per-client compute delay (sleeps "
                        "overlap across drivers — the parallelism the "
                        "autoscaler provisions).")
    p.add_argument("--fleet_target", type=float, default=0.0,
                   help="Fleet target rounds/s (0 = derive ~1.8x the "
                        "initial fleet's theoretical rate).")
    # ingest micro-mode knobs (INGESTBENCH_r*)
    p.add_argument("--ingest_micro", action="store_true",
                   help="Run ONLY the batch-vs-per-frame decode micro "
                        "(schema-v15 fed_bench rows, "
                        "check=ingest_micro) — every wire scheme x "
                        "--ingest_batches, plus the CRC thread-pool "
                        "A/B at the largest f32 batch.")
    p.add_argument("--ingest_d", nargs="*", type=int,
                   default=[1024, 10 ** 4],
                   help="Frame widths (elems) swept by --ingest_micro: "
                        "the small-frame regime where the per-frame "
                        "Python header trip dominates, and the scaling "
                        "cells' d_shard at S=1 where CRC+memcpy do.")
    p.add_argument("--ingest_batches", nargs="*", type=int,
                   default=[8, 64, 256],
                   help="Frame-batch sizes for --ingest_micro.")
    p.add_argument("--ingest_reps", type=int, default=5,
                   help="Timing reps per --ingest_micro cell (min is "
                        "committed).")
    p.add_argument("--json", type=str, default=None,
                   help="Dump rows to this JSON file + the schema-v10 "
                        "JSONL twin (fed_bench records).")
    p.add_argument("--telemetry", type=str, default=None, nargs="?",
                   const="telemetry", metavar="DIR",
                   help="Fleet cell: stream the fed PS's per-role "
                        "telemetry (v10 fed_round events, autoscale "
                        "actions, exchange waits) into "
                        "DIR/fed-ps.telemetry.jsonl — the cluster "
                        "roles' plane, reused verbatim.")
    # hidden child modes
    p.add_argument("--shard_run", action="store_true",
                   help=argparse.SUPPRESS)
    p.add_argument("--shards", type=int, default=1, help=argparse.SUPPRESS)
    p.add_argument("--shard_index", type=int, default=0,
                   help=argparse.SUPPRESS)
    p.add_argument("--client", action="store_true", help=argparse.SUPPRESS)
    p.add_argument("--client_index", type=int, default=1,
                   help=argparse.SUPPRESS)
    p.add_argument("--hosts", type=str, default=None,
                   help=argparse.SUPPRESS)
    p.add_argument("--client_delay_ms", type=float, default=0.0,
                   help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    if args.shard_run:
        return _shard_run(args)
    if args.client:
        return _client_main(args)

    rows = []
    if args.ingest_micro:
        args.skip_bitwise = args.skip_scaling = args.skip_fleet = True
        rows.extend(ingest_micro_cell(args))
    if not args.skip_bitwise:
        row = bitwise_cell(args)
        rows.append(row)
        print(f"s1_bitwise n={row['n']} d={row['d']}: "
              f"equal={row['s1_bitwise_equal']}", flush=True)
    if not args.skip_scaling:
        # hier-median leads: its per-bucket fold is the pure-compute
        # sortnet, so the 1/S curve is clean. hier-krum rides along as
        # the recorded comparison — its selection pays a d-INDEPENDENT
        # ~80us/bucket (the XLA:CPU sort inside the Gram selection)
        # that no shard width shrinks, which visibly flattens its
        # curve (DESIGN.md §19; a negative result, not hidden).
        for gar in args.scaling_gars:
            base = None
            for shards in args.shards_list:
                row = scaling_cell(args, gar, shards)
                if base is None and shards == 1:
                    base = row["round_s"]
                if base is not None and shards > 1:
                    row["speedup"] = round(base / row["round_s"], 3)
                rows.append(row)
                print(f"scaling {row['gar']} S={shards}: "
                      f"round_s={row['round_s']} "
                      f"(sum {row['round_s_sum']}) "
                      f"speedup={row.get('speedup', 1.0)} "
                      f"rss/shard="
                      f"{max(row['per_shard_rss']) / 2 ** 20:.0f}"
                      f" MiB", flush=True)
    if not args.skip_fleet:
        row = fleet_cell(args)
        rows.append(row)
        print(f"fleet: target={row['target_rate']:.2f}/s pre="
              f"{row['pre_rate']}/s recovered={row['recovered_rate']}/s "
              f"active {row['active_initial']}->{row['active_final']} "
              f"(+{row['spawns']})", flush=True)

    if args.json:
        with open(args.json, "w") as fp:
            json.dump(rows, fp, indent=1)
        from ...telemetry import exporters

        jsonl_path = os.path.splitext(args.json)[0] + ".jsonl"
        with exporters.JsonlExporter(jsonl_path) as exp:
            for row in rows:
                exp.write(exporters.make_record("fed_bench", **row))
    return rows


if __name__ == "__main__":
    main(sys.argv[1:])
