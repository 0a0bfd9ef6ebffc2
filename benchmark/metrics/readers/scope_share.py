"""The device time of the leaf operations that the program's own names
pick out, over the time the first device was busy in the traced window
(``event_share.py``'s denominator), in percent.

``args``: ``scopes`` (a list of the vocabulary's names, ``moe`` standing
for the expert layer whole; null: any), ``phases`` (of ``forward``,
``recompute``, ``backward``, ``optimizer``, ``no-path``; null: any),
``module`` (a regular expression on the program's name,
``^jit_chunk_prefill``; null: any); ``"named": true`` reads the share
that lies under ANY vocabulary scope.  The names come from the trace's
own operation metadata (``harness/trace_meta.py``): a scope is read off
the ``tf_op`` path the compiler kept.  Nothing matched, or no operation
of the trace carries a path: no metric."""

from harness import trace_meta


def read(env, args):
    # decoded once a run however many metrics read it: ``env`` is the one
    # object every reader of the run is handed
    if "trace_meta" not in env:
        env["trace_meta"] = trace_meta.of_cell(env["cell"].name)
    meta = env["trace_meta"]
    if meta is None or not meta.busy_ns or not meta.has_paths():
        return None
    idx = meta.select(args.get("scopes"), args.get("phases"),
                      args.get("module"), bool(args.get("named")))
    if not idx:
        return None
    return 100.0 * float(meta.dur[idx].sum()) / meta.busy_ns
