"""The whole step's share of the chip's peak while the device is busy: model
FLOPs (forward, dx and dw of every conv and dense layer, counted from shapes
by `harness.flops`) of the steps that lie in the traced window, over the
seconds in which an operation ran on the device there (the profiler's trace,
averaged over the chips), over chips x peak bf16 FLOP/s. In %. Idle time is
not in it: `device_idle_share` has that."""

from harness import flops, peaks


def read(trace, facts):
    if facts["device"]["platform"] == "cpu":
        return None  # a CPU rehearsal has no peak to be a share of
    steps, busy = trace["fullest"]["steps"], trace["busy_s"]
    if not steps or busy <= 0:
        return None
    done = steps * facts["images_per_step"]
    rate = done * flops.train_flops_per_image(facts["config"]) / busy
    peak = peaks.peak(facts["device"]["kind"], "bf16_flops")
    return 100.0 * rate / (facts["chips"] * peak)
