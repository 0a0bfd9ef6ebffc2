"""A grouped kernel's share of its roofline, in percent, where both the
rows a call works on and the groups whose weights it reads are decided at
run time (serving: a decode tick brings an expert under a row, a chunk
tick hundreds).  From the program's own spans ``args["span"]`` that
started in the traced window, one a tick, come ``args["rows"]`` and
``args["touched"]``, each summed over the expert layers of the tick; from
the model file's ``kernel_work`` come ``flops_per_row``, ``bytes_per_row``,
``bytes_per_expert`` (one group's weights), ``calls_per_layer`` grouped
products a tick runs in each of ``layers`` layers.  The least time of a
call is the larger of its rows' FLOPs over the bf16 peak and its rows'
bytes plus the weights of the groups TOUCHED (not of all held) over the
memory bandwidth; a tick's least time is that over its calls, the window's
the mean tick's times the ticks whose events the trace holds; the share is
that over the events' device time.  No such span, no such event, no clock:
no metric."""

from harness import spans


def read(env, args):
    found = spans.window(env, args["fence"])
    if found is None:
        return None
    recs, (lo, hi), _offset = found
    ticks = [(a.get(args["rows"]), a.get(args["touched"]))
             for a in ((r.attrs or {}) for r in spans.named(recs, args["span"])
                       if lo <= r.start_ns <= hi)]
    ticks = [(r, t) for r, t in ticks if r is not None and t is not None]
    plane = env["planes"][0]
    idx = plane.matching(args["events"])
    if not ticks or not idx:
        return None
    work = env["model"].kernel_work(env["config"], env["mix"], args["work"])
    peaks = env["peaks"]
    layers, calls = work["layers"], work["calls_per_layer"]

    def least(rows, touched):
        rows, touched = rows / layers, touched / layers
        return layers * calls * max(
            work["flops_per_row"] * rows / peaks["bf16_flops_per_s"],
            (work["bytes_per_row"] * rows
             + work["bytes_per_expert"] * touched) / peaks["hbm_bytes_per_s"])

    mean_tick = sum(least(r, t) for r, t in ticks) / len(ticks)
    took = float(plane.op_dur[idx].sum()) * 1e-9
    return 100.0 * mean_tick * len(idx) / (layers * calls) / took \
        if took else None
