"""Median, in milliseconds, over successive executions of one compiled
program on the first device, of either the idle gap between them
(``what: gap``: end of one to start of the next) or their period
(``what: period``: start to start)."""


from harness.stats import median


def read(env, args):
    runs = env["planes"][0].module_runs(args["module"])
    if len(runs) < 2:
        return None
    if args.get("what", "gap") == "gap":
        values = [max(0.0, b[0] - a[1]) for a, b in zip(runs, runs[1:])]
    else:
        values = [b[0] - a[0] for a, b in zip(runs, runs[1:])]
    return median(values) * 1e-6
