"""Device time per step of the operations wholly in ``model.ssm_scan`` in
ms: the selective scans, forward, recomputed and backward (`ops/scan.py`;
`harness.model_map`). None where the program names no such scope."""

from harness import model_map


def read(trace, facts):
    return model_map.scopes_ms(trace, facts, ("ssm_scan",))
