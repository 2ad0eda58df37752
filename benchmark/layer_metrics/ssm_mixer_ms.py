"""Device time per step of the operations wholly in ``model.ssm_proj``,
``model.ssm_conv`` or ``model.ssm_scan`` in ms: the whole of every Mamba
mixer — projections, the memory's gate, convolution, scan —, forward,
recomputed and backward, operations that mix two of the three included
(`harness.model_map`). None where the program names no such scope."""

from harness import model_map

SCOPES = ("ssm_proj", "ssm_conv", "ssm_scan")


def read(trace, facts):
    return model_map.scopes_ms(trace, facts, SCOPES)
