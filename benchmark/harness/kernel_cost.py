"""Bytes a coordinate-wise rule must move, from shapes alone.

Whatever implements it, a coordinate-wise rule over n rows of d values reads
every row once and writes one row: ``(n + 1) * d * itemsize`` bytes. A folded
attack's fake row is a remap of rows already read and adds none. The rule is
bandwidth-bound (a few compares per byte), so its least time is bytes over
the chip's peak HBM bandwidth.
"""

import jax.numpy as jnp


def coordinate_rule_bytes(rows, d, dtype):
    return (rows + 1) * d * jnp.dtype(dtype).itemsize


def coordinate_rule_least_seconds(rows, d, dtype, hbm_bytes_per_s):
    return coordinate_rule_bytes(rows, d, dtype) / hbm_bytes_per_s
