"""Device milliseconds a serving step spends in one kernel: the seconds
of the operation keys holding ``kernel`` in the traced window
(``ev.trace["op_seconds"]``) over the serving steps the StepLog holds
inside it.  None for a run that was not traced, a trace without such an
operation, or a traced stretch without a step."""
from .steplog_stat import serving_steps


def read(ev, kernel):
    tr = ev.trace
    if not tr or not tr["busy_s"]:
        return None
    seconds = sum(v for k, v in tr["op_seconds"].items() if kernel in k)
    steps = [s for s in serving_steps(ev) if tr["t0"] <= s["t"] < tr["t1"]]
    if not seconds or not steps:
        return None
    return 1e3 * seconds / len(steps)
