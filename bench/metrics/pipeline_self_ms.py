"""Stream pipeline (``serving/stream.py``): wall per timed batch spent
outside ``submit_window`` (windowing, event heap, the generator), ms."""


def read(run):
    decide = run.per_window("decide")
    if not decide:
        return None
    return (run.wall_s - sum(decide.values())) / len(decide) * 1e3
