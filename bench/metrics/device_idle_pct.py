"""Device: share of the traced window in which no operation ran on the
device, %.  Nothing to read without a device in the trace."""


def read(run):
    tr = run.trace
    if not tr or not tr["devices"] or not run.trace_window_s:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / run.trace_window_s)
