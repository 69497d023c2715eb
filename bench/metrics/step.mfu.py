"""Model FLOPs of the traced window's steps (``flops.per_token``) over the
traced window's length times the chips times the chip's bf16 peak, in %."""


def read(run):
    tr = run["trace"]
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * run["flops_per_token"] * run["tokens"] / (
        tr["window_s"] * run["chips"] * run["peak_flops"])
