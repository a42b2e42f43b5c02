"""mfu: model FLOPs of the traced generations over the traced window's
seconds times the chips times the chip's peak (bf16), in percent.
Model FLOPs are 2 per MAC for every evaluated (key, test sample) and 6
per MAC for every trained (key, sample, epoch), from the supernet
family's own MAC count (``sample_macs`` of ``bench/families/<family>.py``,
summed by ``bench/yardstick.py``); padding and recompute do not count."""


def read(rec):
    tr = rec["trace"]
    if not tr or tr["window_s"] <= 0 or rec["flops"] <= 0:
        return None
    peak = rec["peaks"]["bf16_flops"] * rec["chips"]
    return 100.0 * rec["flops"] / (tr["window_s"] * peak)
