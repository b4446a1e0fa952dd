"""Mean distinct keys per job that the sorted keyed stage output (the
``stage<i>.distinct_keys`` counter): the job's distinct canonical
21-mers."""
from bench import sortagg


def read(run):
    return sortagg.distinct_keys(run)
