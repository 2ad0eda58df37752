"""TPU kernel library (Pallas) for the coordinate-wise GAR hot path.

The reference ships hand-written CUDA kernels for exactly this layer
(pytorch_impl/libs/native/py_median/median.cu, py_bulyan/bulyan.cu — SURVEY
P13): the GAR math that sweeps the full d-dimensional gradient (d ≈ 1.1e7 for
ResNet-18) rather than the tiny (n, n) score matrices. On TPU the equivalents
are Pallas kernels: each kernel makes ONE pass over HBM, streaming blocks of
the stack through VMEM where the gradient pass left it (worker axis leading,
rows upcast in VMEM, a folded attack's fake row a second operand) and running
an in-register odd-even transposition sorting network over the small n axis
on the VPU — no (n, d) re-layout, no XLA variadic sort, no second pass for
the selection step.

`ops.attention` (imported by the model that uses it, `models/lfm2.py`, not
from here) is the same idea for a token model's attention: blockwise causal
grouped-query kernels that never write the (t, t) scores to HBM.
`ops.grouped` (imported there too) is the held experts' grouped matmul:
rows sorted by expert against each expert's weights, forward and both
gradients, with tiles chosen from the shapes so that an expert's weights are
fetched once; ``jax.lax.ragged_dot`` is its spec and its fallback.
`ops.route` (imported there too) moves that layer's held rows alone: a
gather into the sorted order and a weighted sum back per token whose grids
end at the last held pair, in place of permuting every (token, expert) row.
`ops.scan` (imported by `models/phi4flash.py`; `models/lfm2.py` keeps the
names its kernels give what they keep) is a Mamba layer's selective
scan: a forward and a backward kernel that hold the state on chip across a
sequence's positions, and the chunked loop where they do not apply.

Public entry points dispatch by backend: the Pallas path on TPU (or when
forced via ``interpret=True`` for CPU testing), a pure-jnp fallback elsewhere
with identical semantics (the fallback IS the spec; kernels are tested
against it, including NaN propagation and stable tie-breaking).
"""

from .coordinate import (
    MAX_SORT_N,
    averaged_median_mean,
    coordinate_median,
    sortnet_argmin,
    sortnet_argsort,
    sortnet_median,
    sortnet_row_sums,
    sortnet_sort,
    sortnet_top_m,
    sortnet_trimmed_mean,
    trimmed_mean,
    use_pallas,
)

__all__ = [
    "MAX_SORT_N",
    "averaged_median_mean",
    "coordinate_median",
    "sortnet_argmin",
    "sortnet_argsort",
    "sortnet_median",
    "sortnet_row_sums",
    "sortnet_sort",
    "sortnet_top_m",
    "sortnet_trimmed_mean",
    "trimmed_mean",
    "use_pallas",
]
