"""The expert layers' share of their roofline in the
Kimi-Linear-48B-A3B-Instruct session cell: the least time the chip
could take to read the weights of the held experts a step touches and
of the shared expert, every expert layer (`counts_kimilinear.moe_bytes`),
over the device time under the program's scopes `marks`. The experts
touched are the program's own count (`experts_touched`, the mean over
the steps and layers of the checked turns, which `model_kimilinear.judge`
leaves on the cell as `counted`): the traced turns' counters reach no
reader, and random weights route the 32 sessions' tokens unevenly, so the
expectation under uniform routing is not what the kernel reads. The same
work whatever implements it. Nothing under the scopes in the trace, or no
count: nothing returned."""

import sys

from perfbench import counts_kimilinear, harness
from perfbench.readers import session_step_ms_less


def read(context, marks: list):
    seconds = session_step_ms_less.seconds(context, list(marks))
    touched = getattr(context["cell"], "counted", {}).get(
        "experts_touched_mean")
    if seconds <= 0 or touched is None:
        return None
    nbytes = counts_kimilinear.moe_bytes(context["cell"].config, touched,
                                         context["cell"].traffic["n_new"])
    least = (nbytes / harness.peaks_of(context["device"])["hbm_bytes_per_s"]
             * context["calls"])
    print(f"kimilinear_moe: bound by bytes, {touched:.3f} held experts "
          f"touched a layer and step, least {least:.6f} s, measured "
          f"{seconds:.6f} s", file=sys.stderr)
    return 100.0 * least / seconds
