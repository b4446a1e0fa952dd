"""Mean seconds per job of the executor's ``device_wait`` phase
(``ActionReport.phases``): the dispatched program running on the chip."""


def read(run):
    waits = [a.phases["device_wait"] for a in run.done
             if "device_wait" in a.phases]
    return sum(waits) / len(waits) if waits else None
