"""Device milliseconds from the start of a request's program to the end
of its last operation under `lm.prefill` or `lm.first_token`: the
median over the traced requests. The host cannot see it, because a
request is one program."""

import statistics

from perfbench import scope_reader


def read(context):
    times = (scope_reader.of(context) or {}).get("first_token_s")
    return 1e3 * statistics.median(times) if times else None
