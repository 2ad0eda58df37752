"""Coordinate-wise trimmed-mean GAR (beyond-reference addition).

The reference library does not ship trimmed mean, but its own evaluation
plans name it alongside Median (this repo's BASELINE.json north-star
configs: "Median vs Trimmed-Mean"), and it is the third classical
coordinate-wise robust estimator (Yin et al., ICML'18) next to the
reference's median (median.py) and Bulyan's averaged-median phase
(bulyan.py:77-84). Semantics: per coordinate, drop the f largest and f
smallest values and average the middle n-2f.

TPU form: dispatches to the fused Pallas sort+trim+mean kernel
(garfield_tpu/ops/coordinate.py, one HBM pass) like the median rule; jnp
sort elsewhere. NaN values sort last, so up to f NaNs per coordinate land
in the trimmed tail and do not contaminate the result.
"""

import math

from . import register
from ._common import as_stack, num_gradients, tree_coordinatewise


def aggregate(gradients, f, **kwargs):
    """Mean of the middle n-2f values per coordinate."""
    from .. import ops

    return ops.trimmed_mean(as_stack(gradients), f)


def tree_aggregate(stacked_tree, f, key=None, **kwargs):
    """Tree-mode twin (r3): coordinate-wise, so per-leaf like median's
    (see median.tree_aggregate for the chip measurement)."""
    from .. import ops

    return tree_coordinatewise(
        lambda g: ops.trimmed_mean(g, f), stacked_tree, name="tmean"
    )


def tree_aggregate_ext(stacked_tree, extra_tree, row_map, row_scale, f,
                       key=None, **kwargs):
    """Folded-attack twin (parallel/fold.py): per-leaf trimmed mean over
    the raw stacked tree with the fake row beside it, remap applied
    in-register by the kernel (see median.tree_aggregate_ext)."""
    from .. import ops

    return tree_coordinatewise(
        lambda g, e=None: ops.trimmed_mean(
            g, f, extra=e, row_map=row_map, row_scale=row_scale
        ),
        stacked_tree, extra_tree, name="tmean",
    )


def check(gradients, f, **kwargs):
    n = num_gradients(gradients)
    if n < 1:
        return f"expected at least one gradient to aggregate, got {gradients!r}"
    if not isinstance(f, int) or f < 1 or n < 2 * f + 1:
        return (
            f"invalid number of Byzantine gradients to tolerate, got f = {f!r}, "
            f"expected 1 <= f <= {(n - 1) // 2}"
        )
    return None


def upper_bound(n, f, d):
    """Same family bound as coordinate-wise median, 1/sqrt(n - f)."""
    return 1 / math.sqrt(n - f)


register("tmean", aggregate, check, upper_bound=upper_bound,
         tree_aggregate=tree_aggregate, tree_aggregate_ext=tree_aggregate_ext)
