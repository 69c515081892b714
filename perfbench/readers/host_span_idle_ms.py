"""Milliseconds a call in which the first device runs nothing while the
host is inside the program's `lm.shard_batch` span (placing the next
batch). Host and device planes agree to about a millisecond, so a span
shorter than that reads low. No such span in the trace: nothing
returned."""

from perfbench import scope_reader


def read(context):
    scopes = scope_reader.of(context)
    if not scopes or scopes["feed_idle_s"] is None:
        return None
    return 1e3 * scopes["feed_idle_s"] / scopes["calls"]
