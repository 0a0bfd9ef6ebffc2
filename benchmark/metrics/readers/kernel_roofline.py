"""A kernel's share of its roofline, in percent: the least time the chip
could take for the work the algorithm needs (from shapes, by the model
file's ``kernel_work``; the larger of FLOPs over the bf16 peak and bytes
over the memory bandwidth) times the calls, over the device time of the
events that implement it.  The events are found by the regular
expressions in the metric's file; ``events_per_call`` says how many
events one call leaves (1 for a Pallas kernel).  Nothing matched: no
metric."""


def read(env, args):
    plane = env["planes"][0]
    peaks = env["peaks"]
    least = took = 0.0
    for kernel in args["kernels"]:
        idx = plane.matching(kernel["events"])
        if not idx:
            continue
        work = env["model"].kernel_work(env["config"], env["mix"],
                                        kernel["work"])
        calls = len(idx) / float(kernel.get("events_per_call", 1))
        least += calls * max(work["flops"] / peaks["bf16_flops_per_s"],
                             work["bytes"] / peaks["hbm_bytes_per_s"])
        took += float(plane.op_dur[idx].sum()) * 1e-9
    if not took:
        return None
    return 100.0 * least / took
