"""Child process for the multi-host (DCN) integration test.

Each process is one "host" of a 2-process jax.distributed cluster (CPU
backend, 4 virtual devices per process -> global 8-device mesh). It
bootstraps through the framework's ClusterConfig/init_distributed path,
then runs the Byzantine-resilient aggregation core — per-slot gradient
rows, a lie attack, Multi-Krum — as one SPMD program whose all_gather
crosses the process boundary, and prints the (replicated) aggregate.

Usage: python multihost_child.py <config.json>
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_num_cpu_devices", 4)


def main(config_path):
    import numpy as np

    from garfield_tpu.utils import multihost

    cfg = multihost.ClusterConfig(config_path)
    nproc, pid = multihost.init_distributed(cfg)
    assert nproc == 2, nproc

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from garfield_tpu import aggregators
    from garfield_tpu.attacks import apply_gradient_attack
    from garfield_tpu.parallel import mesh as mesh_lib

    n, d, f = 8, 4096, int(cfg.garfield.get("fw", 2))
    gar = aggregators.gars[cfg.garfield.get("gar", "krum")]
    mesh = mesh_lib.make_mesh({"workers": n})
    byz_mask = jnp.arange(n) >= n - f

    # Per-slot gradient rows: deterministic, same on every process.
    rows = np.random.default_rng(1234).standard_normal((n, d)).astype(np.float32)
    per_host = n // nproc
    x = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("workers")),
        rows[pid * per_host : (pid + 1) * per_host],  # this host's slots
    )

    def step(local_rows):
        stack = jax.lax.all_gather(local_rows, "workers", tiled=True)
        stack = apply_gradient_attack(
            "lie", stack, byz_mask, key=jax.random.PRNGKey(0)
        )
        return gar.unchecked(stack, f=f)

    aggr = jax.jit(
        jax.shard_map(
            step, mesh=mesh, in_specs=P("workers"), out_specs=P(),
            check_vma=False,
        )
    )(x)
    out = np.asarray(jax.device_get(aggr))
    print(f"AGG {pid} {float(out.sum()):.6f} {float(np.abs(out).max()):.6f}",
          flush=True)

    # Host-level wait-n-f exchange (T1/T2/T9 live path): publish this host's
    # serialized aggregate over TCP, block on the native MRMW register for
    # the peer's, and verify both hosts hold the identical replicated result
    # — the DCN analog of ByzSGD's model gather (server.py:161-184).
    ex_hosts = cfg.garfield.get("exchange")
    if ex_hosts:
        from garfield_tpu.utils.exchange import PeerExchange

        with PeerExchange(pid, ex_hosts) as ex:
            ex.publish(0, out.tobytes())
            got = ex.collect(0, q=len(ex_hosts), timeout_ms=60_000)
        peers_equal = all(
            np.array_equal(np.frombuffer(p, np.float32), out)
            for p in got.values()
        )
        print(f"EXCHANGE {pid} ok={peers_equal} n={len(got)}", flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
