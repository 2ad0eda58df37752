"""Model/gradient transfer latency over the mesh.

Counterpart of ``pytorch_impl/applications/benchmarks/rpc_bench.py``
(:95-118): the reference measures RPC model-fetch latency vs model dimension
d and node count n. The SPMD equivalent of "every PS pulls every model /
every worker's gradient" is one all_gather over the mesh axis, so this
benchmark times a jit'd all_gather of a (d,)-vector per device across d and
mesh sizes — the ICI-bandwidth number that bounds every topology's step.

  python -m garfield_tpu.apps.benchmarks.transfer_bench --ds 1000 1000000
"""

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ...parallel import mesh as mesh_lib
from ...utils import profiling


def bench_gather(mesh, d, reps, trials=1):
    axis = mesh.axis_names[0]
    k = mesh.shape[axis]

    # Dependency-chained paired-reps timing (see gar_bench.bench_one): each
    # iteration all_gathers, then takes its OWN chunk back out of the
    # gathered stack so the next iteration depends on the collective without
    # adding a k*d reduction to the measured span (the fold reads d elements,
    # 1/k of the gather payload, so the bandwidth number stays honest).
    def gather_fold(x_local):
        gathered = jax.lax.all_gather(x_local, axis, tiled=False)
        return jax.lax.dynamic_index_in_dim(
            gathered, jax.lax.axis_index(axis), axis=0, keepdims=False
        )

    fn = jax.jit(
        jax.shard_map(
            gather_fold, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
            check_vma=False,
        )
    )
    x0 = fn(jnp.zeros((k, d), jnp.float32))
    np.asarray(x0[0, :1])  # compile + warm + drain queue

    def timed(m):
        x = x0
        t0 = time.perf_counter()
        for _ in range(m):
            x = fn(x)
        np.asarray(x[0, :1])
        return time.perf_counter() - t0

    # gar_bench r7 parity: the committed value is the MIN over ``trials``
    # independent min-of-pairs measurements (VERDICT r4 #3 min-over-k —
    # co-tenant interference only ever adds time, so the minimum is the
    # best estimate of the collective itself).
    vals = [
        profiling.paired_reps(timed, reps, pairs=4, agg="min")
        for _ in range(max(1, trials))
    ]
    vals = [v for v in vals if v is not None]
    return min(vals) if vals else None


def main(argv=None):
    p = argparse.ArgumentParser(description="collective transfer benchmark")
    p.add_argument("--ds", nargs="*", type=int,
                   default=[10 ** k for k in range(2, 8)])
    p.add_argument("--reps", type=int, default=20)
    p.add_argument("--trials", type=int, default=3,
                   help="Independent min-of-pairs timing trials per cell; "
                        "the committed value is the minimum (gar_bench r7 "
                        "parity — min-over-k), recorded per row.")
    p.add_argument("--json", type=str, default=None,
                   help="Also dump results to this JSON file (plus the "
                        "schema-versioned telemetry JSONL twin at the same "
                        "path with a .jsonl suffix).")
    args = p.parse_args(argv)

    n_dev = len(jax.devices())
    sizes = sorted({s for s in (2, 4, 8, n_dev) if 1 < s <= n_dev})
    results = []
    for k in sizes:
        mesh = mesh_lib.make_mesh({"workers": k}, devices=jax.devices()[:k])
        for d in args.ds:
            latency = bench_gather(mesh, d, args.reps, trials=args.trials)
            if latency is None:  # below the host's noise floor (paired_reps)
                print(f"k={k} d={d:<9} below noise floor", flush=True)
                results.append({"devices": k, "d": d, "latency_s": None,
                                "below_noise_floor": True,
                                "trials": args.trials})
                continue
            payload = k * d * 4
            row = {
                "devices": k, "d": d, "latency_s": latency,
                "gather_gbit": profiling.convert_to_gbit(payload),
                "gbit_per_s": profiling.convert_to_gbit(payload) / latency,
                "trials": args.trials,
            }
            results.append(row)
            print(f"k={k} d={d:<9} {latency * 1e6:9.1f} us "
                  f"{row['gbit_per_s']:8.2f} Gbit/s", flush=True)
    if args.json:
        with open(args.json, "w") as fp:
            json.dump(results, fp, indent=1)
        # Schema-versioned JSONL twin (gar_bench r7 parity): validated by
        # the tier-1 schema check, so a malformed sweep fails loudly.
        import os

        from ...telemetry import exporters

        jsonl_path = os.path.splitext(args.json)[0] + ".jsonl"
        with exporters.JsonlExporter(jsonl_path) as exp:
            for row in results:
                exp.write(exporters.make_record(
                    "transfer_bench",
                    devices=row["devices"], d=row["d"],
                    latency_s=row["latency_s"],
                    gbit_per_s=row.get("gbit_per_s"),
                    below_noise_floor=row.get("below_noise_floor", False),
                    trials=row["trials"],
                ))
    return results


if __name__ == "__main__":
    main(sys.argv[1:])
