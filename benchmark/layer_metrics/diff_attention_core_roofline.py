"""The differential attention cores' share of the chip's peak, in %: the
FLOPs their equations need in one step (`harness.diff_attention_cost`:
bound by operations) over the device time per step of every operation that
holds an instruction of ``model.window_attention``,
``model.full_attention`` or ``model.cross_attention`` — the mixed ones
included, so that time left out can never push the share over 100 — over
the peak bf16 FLOP/s. None where there is no map, no such operation, or a
configuration without cross-attention layers (`harness.model_map`)."""

from harness import diff_attention_cost, model_map, peaks

SCOPES = ("window_attention", "full_attention", "cross_attention")


def read(trace, facts):
    if facts["device"]["platform"] == "cpu":
        return None  # a CPU rehearsal has no peak to be a share of
    if "cross_attention" not in facts["config"]["model"].get(
            "layer_types", ()):
        return None
    seconds = sum(model_map.holding_seconds(trace, facts, scope) or 0.0
                  for scope in SCOPES)
    if not seconds:
        return None
    peak = peaks.peak(facts["device"]["kind"], "bf16_flops")
    return 100.0 * diff_attention_cost.core_flops_per_step(
        facts["config"]) / (seconds * facts["chips"] * peak)
