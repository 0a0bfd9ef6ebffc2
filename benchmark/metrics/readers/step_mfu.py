"""The whole step's share of the chip's bf16 peak, in percent: the work
the configuration REQUIRES (from shapes, by the model file's own
function; recompute is never counted) over the time it took on the
device's clock.

``per_run_of``: the counters give ``required_flops_per_step``; the time is
from the start of the first execution of that program to the start of the
last, so whole periods with their gaps (training).  Without it the
counters give ``required_flops`` of everything processed in the traced
window, and the time is the traced window (serving)."""


def read(env, args):
    peak = env["peaks"]["bf16_flops_per_s"] * len(env["planes"])
    c = env["counters"]
    if "per_run_of" in args:
        runs = env["planes"][0].module_runs(args["per_run_of"])
        if len(runs) < 2:
            return None
        seconds = (runs[-1][0] - runs[0][0]) * 1e-9
        flops = c["required_flops_per_step"] * (len(runs) - 1)
    else:
        seconds = env["summary"]["window_s"]
        flops = c.get("required_flops")
        if not flops:
            return None
    return 100.0 * flops / seconds / peak
