"""Milliseconds a call in which a collective runs on a device and no
other operation does (mean over the chips): what overlapping the
collectives with compute could win back. Their `ring` (under `lm.ring`)
and `grad` (the rest) parts and the collectives' whole time are in the
table that `scope_reader` prints. No collective in the trace: nothing
returned."""

from perfbench import scope_reader


def read(context):
    scopes = scope_reader.of(context)
    if not scopes or not sum(scopes["collective_s"].values()):
        return None
    return 1e3 * scopes["exposed_s"] / scopes["calls"]
