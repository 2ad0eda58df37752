"""The attention cores' share of the chip's peak, in %: the FLOPs the two
contractions of every attention layer need in one step at the visible pairs
of each layer's kind (`harness.attention_cost`: bound by operations) over
the device time per step of every operation that holds an instruction of
``model.window_attention`` or of ``model.full_attention`` — the mixed ones
included, so that time left out can never push the share over 100 — over
the peak bf16 FLOP/s. None where there is no map or no such operation
(`harness.model_map`)."""

from harness import attention_cost, model_map, peaks

SCOPES = ("window_attention", "full_attention")


def read(trace, facts):
    if facts["device"]["platform"] == "cpu":
        return None  # a CPU rehearsal has no peak to be a share of
    seconds = sum(model_map.holding_seconds(trace, facts, scope) or 0.0
                  for scope in SCOPES)
    if not seconds:
        return None
    peak = peaks.peak(facts["device"]["kind"], "bf16_flops")
    return 100.0 * attention_cost.core_flops_per_step(facts["config"]) / (
        seconds * facts["chips"] * peak)
