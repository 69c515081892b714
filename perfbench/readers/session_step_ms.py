"""Device milliseconds a scanned position under the program's scopes
named in `marks` (`session_scopes.seconds_under`, summed): the session
driver reduces its own trace. Nothing under them in the trace (a
program without the scopes): nothing returned."""


def read(context, marks: list):
    sub = context.get("sub_scopes") or {}
    seconds = sum(sub.get(m, 0.0) for m in marks)
    if not seconds:
        return None
    steps = context["calls"] * context["cell"].traffic["n_new"]
    return 1e3 * seconds / steps
