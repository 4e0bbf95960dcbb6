"""Device: time of the program ``_fused_solve`` per batch in the traced
part of the window, from the device trace, ms.  Nothing to read without a
device in the trace."""


def read(run):
    tr = run.trace
    if not tr or not tr["devices"] or not tr["program_s"] or not run.timed:
        return None
    n = tr["spans"].get("decide", 0)
    if not n:
        return None
    return sum(tr["program_s"].values()) / n * 1e3
