"""A grouped kernel's share of its roofline, in percent, where the rows a
call works on are decided at run time: the rows come from the program's
own spans (the attribute ``args["rows"]`` of the spans ``args["span"]``
that started in the traced window, a step's rows summed over the expert
layers) and the work a row needs from the model file's ``kernel_work``
(``flops_per_row``, ``bytes_per_row``, ``bytes_per_call`` for the weights a
call reads once, ``calls_per_layer`` grouped products a step runs in each
of ``layers`` layers).  The least time of a call is the larger of its
FLOPs over the bf16 peak and its bytes over the memory bandwidth, at the
mean rows a call had in the window; the share is that times the events
over their device time.  A seed whose router sends fewer rows here so
cannot read over its roofline.  No such span, no such event, no clock:
no metric."""

from harness import spans


def read(env, args):
    found = spans.window(env, args["fence"])
    if found is None:
        return None
    recs, (lo, hi), _offset = found
    rows = [(r.attrs or {}).get(args["rows"])
            for r in spans.named(recs, args["span"])
            if lo <= r.start_ns <= hi]
    rows = [r for r in rows if r is not None]
    plane = env["planes"][0]
    idx = plane.matching(args["events"])
    if not rows or not idx:
        return None
    work = env["model"].kernel_work(env["config"], env["mix"], args["work"])
    peaks = env["peaks"]
    rows_per_call = sum(rows) / len(rows) / work["layers"]
    least = max(
        work["flops_per_row"] * rows_per_call / peaks["bf16_flops_per_s"],
        (work["bytes_per_row"] * rows_per_call + work["bytes_per_call"])
        / peaks["hbm_bytes_per_s"])
    took = float(plane.op_dur[idx].sum()) * 1e-9
    return 100.0 * least * len(idx) / took if took else None
