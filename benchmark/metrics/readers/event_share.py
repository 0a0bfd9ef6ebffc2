"""The device time of the operations whose names match ``args["events"]``
over the time the first device was busy in the traced window, in percent:
a kernel's share of the step.  Nothing matched: no metric."""


def read(env, args):
    plane = env["planes"][0]
    idx = plane.matching(args["events"])
    busy = plane.busy_ns()
    if not idx or not busy:
        return None
    return 100.0 * float(plane.op_dur[idx].sum()) / busy
