"""GAR kernel latency sweep.

Counterpart of ``pytorch_impl/applications/benchmarks/gar_bench.py``
(:41-89): per-GAR latency across n in powers of two, f as allowed by each
rule's contract, d in powers of ten — the same sweep grid, but timed as
jit'd XLA executions (compile excluded) with dependency-chained paired-reps
timing (see ``bench_one``; JSON key ``latency_s``) and, for the
``native-*`` rules, as C++ host kernels. Each cell's chain consumes the
aggregate through a NONLINEAR guard (the r5 microbench-trap rule — a
linear consumer lets XLA rewrite the timed reductions away) and the
committed value is the min over ``--trials`` independent measurements
(VERDICT r4 #3), recorded in the rows as ``dce_guard``/``trials``.

  python -m garfield_tpu.apps.benchmarks.gar_bench --gars krum median \\
      --ns 4 16 64 --ds 10 1000 100000 --reps 10 --json out.json
"""

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from ... import aggregators
from ...aggregators import bulyan as _bulyan
from ...aggregators import hierarchy
from ...aggregators import krum as _krum
from ...aggregators._common import distances_from_gram
from ...utils import profiling
from ..common import peak_rss_bytes

# Practical bound for brute's exhaustive enumeration, like the reference's
# sweep bound (gar_bench.py:51 keeps n small for brute).
BRUTE_MAX_N = 25

# bench_one sentinel: the rule's contract rejects this (n, f) combination.
INCOMPATIBLE = object()


def max_f(rule, n):
    """Largest f each rule's contract admits (aggregators/*.check; the
    hier-* rules report their composed capacity, aggregators/hierarchy)."""
    if rule.startswith("hier"):
        try:
            bucket_gar, top_gar = hierarchy.parse_hier_name(rule)
        except ValueError:
            bucket_gar, top_gar = "krum", None  # the env-configured alias
        cap = hierarchy.max_tolerated_f(n, bucket_gar, top_gar)
        return max(cap or 0, 0)
    bounds = {
        "krum": (n - 3) // 2,
        "bulyan": (n - 3) // 4,
        "brute": (n - 1) // 2,
        "condense": (n - 2) // 2,
        "aksel": (n - 1) // 2,
        "median": (n - 1) // 2,
        "tmean": (n - 1) // 2,
        "average": (n - 1) // 2,
        "cclip": (n - 1) // 2,
    }
    base = rule.split("native-")[-1]
    return max(bounds.get(base, 0), 0)


def bench_one(gar, n, f, d, reps, key, trials=1):
    g = jax.random.normal(key, (n, d), jnp.float32)
    kwargs = {"f": f} if f else {}
    try:
        if gar.check(np.zeros((n, 2), np.float32), **kwargs) is not None:
            return INCOMPATIBLE
    except TypeError:
        pass
    # Paired-reps timing (utils/profiling.paired_reps): the constant cost
    # of ending a chain in a host readback cancels in the difference:
    #   - dependency-chain the iterations ((n, d) -> (n, d) by writing the
    #     aggregate back into row 0) so they cannot be overlapped;
    #   - run the chain at ``reps`` and ``2*reps`` with a readback sync each,
    #     and report the difference / reps — the per-sync constant cancels.
    # The chain input is donated so the row-0 write updates the buffer in
    # place instead of copying the whole (n, d) stack every iteration (which
    # would bias cheap rules); each timed run starts from a fresh device
    # buffer because donation consumes the previous one.
    #
    # DCE guard (VERDICT r4 #3 + the r5 microbench-trap rule): the
    # aggregate is consumed through a cheap NONLINEAR elementwise map
    # (softsign: a * rsqrt(1 + a^2), one fused VPU pass over d) before the
    # row-0 write-back. A linear consumer lets XLA algebraically rewrite
    # the rule's reductions (r5 traced sum(conv(x, dy)) collapsing into
    # direct reductions — the timed ops vanish from the graph); the
    # nonlinearity pins every aggregate coordinate as a real data
    # dependency of the next iteration. Bonus: softsign's (-1, 1) range
    # keeps the chained stack bounded over thousands of reps.
    def _chain(s):
        a = gar.unchecked(s, **kwargs).astype(jnp.float32)
        guarded = a * jax.lax.rsqrt(1.0 + a * a)
        return s.at[0].set(guarded.astype(s.dtype))

    chain = jax.jit(_chain, donate_argnums=0)
    # np.array/jnp.array (not asarray): on CPU an asarray view would alias
    # the device buffer the next chain() call donates, corrupting s0_host.
    s0_host = np.array(chain(g))  # compile + warm + sync (g donated)

    def timed(k):
        s = jnp.array(s0_host)
        np.asarray(s[0, :1])  # finish H2D transfer + drain queue
        t0 = time.perf_counter()
        for _ in range(k):
            s = chain(s)
        np.asarray(s[0, :1])  # host readback: the only reliable sync
        return time.perf_counter() - t0

    # Two-phase adaptive timing (VERDICT r4 weak #2): sub-ms cells at the
    # configured reps leave the chained run far below the host-sync noise
    # floor, and their committed values bounced >1.3x between sweeps. A
    # coarse estimate sizes reps so the timed chain runs ~0.5 s, then the
    # recorded value is the MIN over ``trials`` independent min-of-pairs
    # measurements (VERDICT r4 #3's min-over-k: co-tenant interference
    # only adds time; the minimum estimates the kernel itself).
    est = profiling.paired_reps(timed, reps, pairs=2)
    if est is not None and est * reps < 0.25:
        reps = min(4000, max(reps, int(0.5 / max(est, 1e-7))))
    vals = [
        profiling.paired_reps(timed, reps, pairs=4, agg="min")
        for _ in range(max(1, trials))
    ]
    vals = [v for v in vals if v is not None]
    return min(vals) if vals else None


def hier_bench_one(name, n, f, d, *, bucket_size, wave, trials, seed=0):
    """Time one hierarchical cell END TO END through the streaming reducer:
    full wave-based ingest of n clients plus the cascaded folds plus
    ``finalize`` — the federated arrival pattern, not an (n, d)-resident
    microkernel. Memory stays O(wave · bucket_size · d): client waves are
    generated into two fixed pools cycled through ``push_many`` (generation
    stays OUTSIDE the timed region), so the (n, d) stack never exists —
    at n = 2^17, d = 1e5 that stack alone would be 52 GB.

    DCE guard: finalize()'s host readback is a hard sync, and the returned
    aggregate is still consumed through the softsign map (the r5
    microbench-trap rule) so no consumer-side rewrite can shed it. The
    committed value is the min over ``trials`` full runs (VERDICT r4 #3).
    """
    bucket_gar, top_gar = hierarchy.parse_hier_name(name)
    rng = np.random.default_rng(seed)
    wave_rows = wave * bucket_size
    pools = [rng.normal(size=(wave_rows, d)).astype(np.float32)
             for _ in range(2)]

    def run_once():
        red = hierarchy.StreamingAggregator(
            n, f, bucket_gar=bucket_gar, top_gar=top_gar,
            bucket_size=bucket_size, wave_buckets=wave,
        )
        i = 0
        while i < n:
            pool = pools[(i // wave_rows) % 2]
            take = min(wave_rows, n - i)
            red.push_many(pool[:take])
            i += take
        out = red.finalize()
        guarded = float(np.sum(out * (1.0 / np.sqrt(1.0 + out * out))))
        return guarded, red.plan

    _, plan = run_once()  # compile + warm
    vals = []
    for _ in range(max(1, trials)):
        t0 = time.perf_counter()
        run_once()
        vals.append(time.perf_counter() - t0)
    total = min(vals)
    return {
        "latency_s": total,
        "per_client_s": total / n,
        "bucket_size": bucket_size,
        "wave_buckets": wave,
        "levels": plan.num_levels,
        "num_buckets": plan.num_buckets,
    }


# Selection micro mode (--selection): the Gram-rule selection step in
# isolation, batched across a wave of buckets exactly as the hierarchy's
# vmapped fold runs it. Both impls are explicit ``use_sortnet`` closures
# — NOT the env knob — so each gets its own jit program and the shared
# cache is never poisoned by a trace-time env read (see
# krum._sortnet_select).
SELECTION_RULES = ("krum", "bulyan")
SELECTION_IMPLS = ("sortnet", "xla_sort")


def _selection_fn(rule, f, use_sortnet):
    """(W, s, d) wave -> per-bucket selection weights, the Gram rule's
    selection step only (Gram matmul + scores + ranked pick). Krum emits
    (W, s) one-hot/m weights; Bulyan its (W, rounds, s) phase-1 weight
    matrix — in both cases exactly what the wave fold consumes."""
    if rule == "krum":
        def one(gb):
            acc = jnp.promote_types(gb.dtype, jnp.float32)
            gram = jnp.matmul(gb, gb.T, preferred_element_type=acc)
            return _krum.gram_select(gram, f, use_sortnet=use_sortnet)
    elif rule == "bulyan":
        def one(gb):
            s = gb.shape[0]
            acc = jnp.promote_types(gb.dtype, jnp.float32)
            gram = jnp.matmul(gb, gb.T, preferred_element_type=acc)
            return _bulyan._selection_weight_matrix(
                distances_from_gram(gram), s, f, s - f - 2, jnp.float32,
                use_sortnet,
            )
    else:
        raise ValueError(
            f"--selection supports {SELECTION_RULES}, got {rule!r}"
        )
    return jax.vmap(one)


def selection_bench_one(rule, s, f, d, wave, reps, key, trials, impl):
    """Time one (rule, bucket_size, impl) selection cell: a jitted
    dependency-chained wave of ``wave`` buckets of ``s`` rows, selection
    weights consumed through the softsign DCE guard and written back
    into the stack (the bench_one methodology verbatim — paired reps,
    adaptive sizing, min over trials)."""
    g = jax.random.normal(key, (wave, s, d), jnp.float32)
    sel = _selection_fn(rule, f, impl == "sortnet")

    def _chain(stack):
        w = sel(stack).astype(jnp.float32)
        # Reduce whatever weight shape the rule emits to one scalar per
        # bucket through the nonlinear guard — every weight is a real
        # data dependency of the next iteration's stack.
        guarded = w * jax.lax.rsqrt(1.0 + w * w)
        per_bucket = guarded.reshape(wave, -1).sum(axis=1)
        return stack.at[:, 0, 0].add(per_bucket * 1e-6)

    chain = jax.jit(_chain, donate_argnums=0)
    s0_host = np.array(chain(g))  # compile + warm + sync (g donated)

    def timed(k):
        st = jnp.array(s0_host)
        np.asarray(st[0, :1, :1])  # finish H2D + drain queue
        t0 = time.perf_counter()
        for _ in range(k):
            st = chain(st)
        np.asarray(st[0, :1, :1])  # host readback sync
        return time.perf_counter() - t0

    est = profiling.paired_reps(timed, reps, pairs=2)
    if est is not None and est * reps < 0.25:
        reps = min(4000, max(reps, int(0.5 / max(est, 1e-7))))
    vals = [
        profiling.paired_reps(timed, reps, pairs=4, agg="min")
        for _ in range(max(1, trials))
    ]
    vals = [v for v in vals if v is not None]
    return min(vals) if vals else None


def _selection_main(args):
    """The --selection sweep: (rule x bucket_size x impl) grid, JSON +
    schema-versioned JSONL twin like the other modes."""
    from ...ops import coordinate as _coord

    rules = args.gars or list(SELECTION_RULES)
    sizes = args.sel_buckets or [8, 16, 32]
    # Default d sweep: the legacy 256 anchor plus the attention-shaped
    # regimes (d = heads * d_head * seq — a transformer worker's
    # per-layer activation-gradient granularity): 768 = the gpt_tiny
    # block (48-dim x 16-token copytask window), 3072 = the vit_tiny
    # block (3 heads x 16 d_head x 64 patches). Selection cost is
    # d-linear only through the Gram build, so these rows pin where the
    # transformer family's buckets actually land.
    ds = args.ds or [256, 768, 3072]
    wave = args.hier_wave
    key = jax.random.PRNGKey(0)
    results = []
    for rule in rules:
        for s in sorted(sizes):
            f = max_f(rule, s) if args.f_mode == "max" else min(
                1, max_f(rule, s))
            for d in ds:
                for impl in SELECTION_IMPLS:
                    if impl == "sortnet" and s > _coord.MAX_SORT_N:
                        continue  # the network is bounded; xla row stays
                    key, sub = jax.random.split(key)
                    try:
                        latency = selection_bench_one(
                            rule, s, f, d, wave, args.reps, sub,
                            args.trials, impl,
                        )
                    except Exception as exc:
                        print(f"{rule} s={s} f={f} impl={impl}: SKIP "
                              f"({exc})", file=sys.stderr)
                        continue
                    row = {"gar": rule, "n": s, "f": f, "d": d,
                           "grid": "selection", "impl": impl,
                           "wave_buckets": wave,
                           "latency_s": latency,
                           "per_bucket_s": (None if latency is None
                                            else latency / wave),
                           "trials": args.trials,
                           "dce_guard": "softsign",
                           "peak_rss_bytes": peak_rss_bytes()}
                    if latency is None:
                        row["below_noise_floor"] = True
                        print(f"{rule:>8} s={s:<3} f={f:<3} d={d:<5} "
                              f"impl={impl:<9} below noise floor",
                              flush=True)
                    else:
                        print(f"{rule:>8} s={s:<3} f={f:<3} d={d:<5} "
                              f"impl={impl:<9} "
                              f"{latency * 1e6:9.1f} us/wave  "
                              f"{latency / wave * 1e6:8.2f} us/bucket",
                              flush=True)
                    results.append(row)
    if args.json:
        with open(args.json, "w") as fp:
            json.dump(results, fp, indent=1)
        import os

        from ...telemetry import exporters

        jsonl_path = os.path.splitext(args.json)[0] + ".jsonl"
        with exporters.JsonlExporter(jsonl_path) as exp:
            for row in results:
                exp.write(exporters.make_record(
                    "gar_bench",
                    gar=row["gar"], n=row["n"], f=row["f"], d=row["d"],
                    latency_s=row["latency_s"],
                    grid=row["grid"], impl=row["impl"],
                    wave_buckets=row["wave_buckets"],
                    per_bucket_s=row["per_bucket_s"],
                    below_noise_floor=row.get("below_noise_floor", False),
                    trials=row["trials"], dce_guard=row["dce_guard"],
                    peak_rss_bytes=row["peak_rss_bytes"],
                ))
    return results


def main(argv=None):
    p = argparse.ArgumentParser(description="GAR latency microbenchmark")
    p.add_argument("--gars", nargs="*", default=None)
    p.add_argument("--ns", nargs="*", type=int, default=None)
    p.add_argument("--ds", nargs="*", type=int, default=None)
    p.add_argument("--reps", type=int, default=10)
    p.add_argument("--trials", type=int, default=3,
                   help="Independent min-of-pairs timing trials per cell; "
                        "the committed value is the minimum (VERDICT r4 "
                        "#3 min-over-k — co-tenant noise only adds time).")
    p.add_argument("--f_mode", choices=["max", "one"], default="max",
                   help="f per (rule, n): contract maximum or fixed 1.")
    p.add_argument("--hier", action="store_true",
                   help="Hierarchical federated-scale grid: streaming-"
                        "ingest hier-* rules at n in 2^10..2^17 (defaults; "
                        "override with --gars/--ns/--ds), peak-RSS per "
                        "row, 'hier_bench' JSONL records — HIERBENCH_r*'s "
                        "capture mode.")
    p.add_argument("--selection", action="store_true",
                   help="Selection micro mode: the Gram-rule selection "
                        "step alone (Gram + scores + ranked pick), "
                        "batched over a wave of buckets as the "
                        "hierarchy's vmapped fold runs it, once per "
                        "impl (sortnet vs xla_sort as explicit "
                        "use_sortnet closures). 'gar_bench' rows with "
                        "grid='selection' and an 'impl' field.")
    p.add_argument("--sel_buckets", nargs="*", type=int, default=None,
                   metavar="S",
                   help="With --selection: bucket sizes to sweep "
                        "(default 8 16 32; the sortnet impl requires "
                        "S <= MAX_SORT_N).")
    p.add_argument("--hier_bucket", type=int, default=None,
                   help="Hierarchy bucket size (default MAX_SORT_N=32, "
                        "the Pallas sorting-network sweet spot).")
    p.add_argument("--hier_wave", type=int, default=8,
                   help="Streaming wave width: buckets folded per vmapped "
                        "dispatch.")
    p.add_argument("--flat_baseline", nargs="*", type=int, default=None,
                   metavar="N",
                   help="With --hier: also time the flat krum/median cells "
                        "at these n (same container, same methodology) so "
                        "the artifact carries its own apples-to-apples "
                        "baseline — GARBENCH_r3's flat numbers are a CHIP "
                        "capture (BASELINE.md).")
    p.add_argument("--json", type=str, default=None,
                   help="Also dump results to this JSON file (plus the "
                        "schema-versioned telemetry JSONL twin at the same "
                        "path with a .jsonl suffix — one 'gar_bench'/"
                        "'hier_bench' record per cell, validated by the "
                        "tier-1 schema check).")
    args = p.parse_args(argv)

    if args.selection:
        return _selection_main(args)

    if args.hier:
        names = args.gars or ["hier-krum", "hier-median"]
        ns = args.ns or [2 ** k for k in range(10, 18)]
        ds = args.ds or [10 ** 5]
    else:
        names = args.gars or sorted(
            g for g in aggregators.gars if not g.startswith("hier"))
        ns = args.ns or [2 ** k for k in range(2, 8)]
        ds = args.ds or [10 ** k for k in range(1, 5)]

    key = jax.random.PRNGKey(0)
    results = []

    def flat_cell(name, n, d, trials):
        gar = aggregators.gars[name]
        f = max_f(name, n) if args.f_mode == "max" else min(1, max_f(name, n))
        nonlocal key
        key, sub = jax.random.split(key)
        try:
            latency = bench_one(gar, n, f, d, args.reps, sub, trials=trials)
        except Exception as exc:
            print(f"{name} n={n} f={f} d={d}: SKIP ({exc})", file=sys.stderr)
            return None
        if latency is INCOMPATIBLE:
            return None
        row = {"gar": name, "n": n, "f": f, "d": d,
               "latency_s": latency,
               # provenance: future GARBENCH_r* readers can tell
               # guarded min-over-k sweeps from the r3/r4 format
               "trials": trials, "dce_guard": "softsign",
               "peak_rss_bytes": peak_rss_bytes()}
        results.append(row)
        if latency is None:  # below noise floor (paired_reps)
            row["below_noise_floor"] = True
            print(f"{name:>16} n={n:<4} f={f:<3} d={d:<7} "
                  f"below noise floor", flush=True)
        else:
            print(f"{name:>16} n={n:<4} f={f:<3} d={d:<7} "
                  f"{latency * 1e3:8.3f} ms", flush=True)
        return row

    for name in names:
        if name.startswith("hier"):
            bucket = args.hier_bucket or hierarchy.DEFAULT_BUCKET_SIZE
            # Ascending n: ru_maxrss is a high-water mark, so this order
            # makes the O(buckets) memory profile readable row-to-row.
            for n in sorted(ns):
                f = (max_f(name, n) if args.f_mode == "max"
                     else min(1, max_f(name, n)))
                for d in ds:
                    try:
                        cell = hier_bench_one(
                            name, n, f, d, bucket_size=bucket,
                            wave=args.hier_wave, trials=args.trials,
                        )
                    except Exception as exc:
                        print(f"{name} n={n} f={f} d={d}: SKIP ({exc})",
                              file=sys.stderr)
                        continue
                    row = {"gar": name, "n": n, "f": f, "d": d,
                           "grid": "hier", "trials": args.trials,
                           "dce_guard": "softsign",
                           "peak_rss_bytes": peak_rss_bytes(), **cell}
                    results.append(row)
                    print(f"{name:>16} n={n:<7} f={f:<6} d={d:<7} "
                          f"{cell['latency_s']:8.3f} s total  "
                          f"{cell['per_client_s'] * 1e6:9.1f} us/client  "
                          f"rss {row['peak_rss_bytes'] / 2**20:7.0f} MiB",
                          flush=True)
        else:
            for n in sorted(ns):
                if name.endswith("brute") and n > BRUTE_MAX_N:
                    continue
                for d in ds:
                    flat_cell(name, n, d, args.trials)

    # Same-container flat anchor cells for the hier artifact (reps=1:
    # a flat median at n=512, d=1e5 runs ~7 s PER CALL on this class of
    # host — the paired-reps chain at default reps would take hours).
    if args.hier and args.flat_baseline:
        saved_reps, args.reps = args.reps, 1
        for n in args.flat_baseline:
            for base in ("krum", "median"):
                for d in ds:
                    row = flat_cell(base, n, d, 1)
                    if row is not None:
                        row["grid"] = "flat_baseline"
        args.reps = saved_reps

    if args.json:
        with open(args.json, "w") as fp:
            json.dump(results, fp, indent=1)
        # Schema-versioned JSONL twin (telemetry/exporters.py): the format
        # GARBENCH_r*/HIERBENCH_r* artifacts adopt — the tier-1 schema
        # check validates it, so a malformed sweep fails loudly.
        import os

        from ...telemetry import exporters

        jsonl_path = os.path.splitext(args.json)[0] + ".jsonl"
        with exporters.JsonlExporter(jsonl_path) as exp:
            for row in results:
                if row.get("grid") == "hier":
                    exp.write(exporters.make_record(
                        "hier_bench",
                        gar=row["gar"], n=row["n"], f=row["f"], d=row["d"],
                        bucket_size=row["bucket_size"],
                        levels=row["levels"],
                        num_buckets=row["num_buckets"],
                        latency_s=row["latency_s"],
                        per_client_s=row["per_client_s"],
                        peak_rss_bytes=row["peak_rss_bytes"],
                        wave_buckets=row["wave_buckets"],
                        trials=row["trials"], dce_guard=row["dce_guard"],
                    ))
                else:
                    exp.write(exporters.make_record(
                        "gar_bench",
                        gar=row["gar"], n=row["n"], f=row["f"], d=row["d"],
                        latency_s=row["latency_s"],
                        below_noise_floor=row.get(
                            "below_noise_floor", False),
                        trials=row["trials"], dce_guard=row["dce_guard"],
                        peak_rss_bytes=row["peak_rss_bytes"],
                    ))
    return results


if __name__ == "__main__":
    main(sys.argv[1:])
