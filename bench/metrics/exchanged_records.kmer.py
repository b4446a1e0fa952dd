"""Mean records per job that the keyed hash exchange sent between chips
(the ``stage<i>.exchanged_records`` counters, summed over stages)."""


def read(run):
    per_job = [sum(v for k, v in a.counters.items()
                   if k.endswith(".exchanged_records")) for a in run.done]
    return sum(per_job) / len(per_job) if per_job else None
