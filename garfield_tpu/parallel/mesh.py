"""Device-mesh construction and logical-slot folding.

TPU-native replacement for the reference's process-group plumbing
(pytorch_impl/applications/Garfield_CC/trainer.py:347-380 ``init_groups`` /
``init_processes``): instead of building NCCL/Gloo groups per (PS, workers)
pair, we lay out one ``jax.sharding.Mesh`` whose named axes carry the node
roles ("workers", "ps", "nodes"), and every collective rides the ICI mesh as
an XLA op (all_gather/psum) inside jit.

The reference runs one OS process per logical node; here logical nodes are
*slots folded onto physical devices* (SURVEY §7 "hard parts"): a mesh axis of
size k hosts n >= k logical slots, each device vmapping over its n/k local
slots. ``fold`` computes that factorization.
"""

import math

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["make_mesh", "fold", "replicated", "sharded", "P"]


def make_mesh(axes, devices=None):
    """Build a Mesh from an ordered ``{axis_name: size}`` dict.

    ``size = -1`` for at most one axis means "all remaining devices". Device
    count must equal the product of axis sizes; the axes are laid out in the
    given order over ``jax.devices()`` (ICI-adjacent devices end up adjacent
    on the innermost axis, which is where the gradient all_gather runs).
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    names = list(axes)
    sizes = [axes[n] for n in names]
    if sizes.count(-1) > 1:
        raise ValueError("at most one mesh axis may have size -1")
    if -1 in sizes:
        known = math.prod(s for s in sizes if s != -1)
        if known == 0 or len(devices) % known:
            raise ValueError(
                f"cannot infer -1 axis: {len(devices)} devices, others {known}"
            )
        sizes[sizes.index(-1)] = len(devices) // known
    total = math.prod(sizes)
    if total != len(devices):
        raise ValueError(
            f"mesh {dict(zip(names, sizes))} wants {total} devices, "
            f"got {len(devices)}"
        )
    arr = np.asarray(devices).reshape(sizes)
    return Mesh(arr, tuple(names))


def fold(num_logical, axis_size, what="slots"):
    """Number of logical slots per device shard; requires exact divisibility.

    Reference analog: none — torch runs one process per node. Folding lets n
    logical workers run SPMD on k chips (n % k == 0), each chip vmapping over
    its n/k slots.
    """
    if num_logical % axis_size:
        raise ValueError(
            f"{num_logical} logical {what} do not fold onto a mesh axis of "
            f"size {axis_size} (must divide exactly)"
        )
    return num_logical // axis_size


def replicated(mesh):
    """NamedSharding replicating an array over the whole mesh."""
    return NamedSharding(mesh, P())


def sharded(mesh, *axis_names):
    """NamedSharding splitting an array's leading dims over named axes."""
    return NamedSharding(mesh, P(*axis_names))
