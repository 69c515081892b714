"""Device milliseconds a scanned position under the program's scopes
`marks` and not under `less` (`session_scopes.seconds_under`): what a
block holds beside the parts it names. Nothing left (a program without
the scopes): nothing returned."""


def seconds(context, marks: list, less: list = ()) -> float:
    sub = context.get("sub_scopes") or {}
    return (sum(sub.get(m, 0.0) for m in marks)
            - sum(sub.get(m, 0.0) for m in less))


def read(context, marks: list, less: list):
    under = seconds(context, marks, less)
    if under <= 0:
        return None
    steps = context["calls"] * context["cell"].traffic["n_new"]
    return 1e3 * under / steps
