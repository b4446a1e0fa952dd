"""Input bases of every job that completed, over the time from window
start to the end of the last job, in megabases per second."""


def read(run):
    done = run.done
    if not done or run.window_s <= 0:
        return None
    return sum(a.bases for a in done) / run.window_s / 1e6
