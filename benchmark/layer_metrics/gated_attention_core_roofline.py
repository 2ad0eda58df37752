"""The attention cores' share of the chip's peak where a layer's head count
is its own, in %: the FLOPs the two contractions of every attention layer
need in one step at that layer's heads and the visible pairs of its kind
(`harness.gated_attention_cost`: bound by operations) over the device time
per step of every operation that holds an instruction of
``model.window_attention`` or of ``model.full_attention`` — the mixed ones
included, so that time left out can never push the share over 100 — over
the peak bf16 FLOP/s. None where there is no map, no such operation, or a
configuration without per-layer head counts (`harness.model_map`)."""

from harness import gated_attention_cost, model_map, peaks

SCOPES = ("window_attention", "full_attention")


def read(trace, facts):
    if facts["device"]["platform"] == "cpu":
        return None  # a CPU rehearsal has no peak to be a share of
    if "num_attention_heads_per_layer" not in facts["config"]["model"]:
        return None
    seconds = sum(model_map.holding_seconds(trace, facts, scope) or 0.0
                  for scope in SCOPES)
    if not seconds:
        return None
    peak = peaks.peak(facts["device"]["kind"], "bf16_flops")
    return 100.0 * gated_attention_cost.core_flops_per_step(
        facts["config"]) / (seconds * facts["chips"] * peak)
