"""Mean seconds per job of the program's ``ingest`` span (``io/ingest``:
fetch, frame, pack and device_put of the job's reads)."""


def read(run):
    spans = [e["dur"] / 1e6 for e in run.spans
             if e.get("ph") == "X" and e["name"] == "ingest"]
    return sum(spans) / len(spans) if spans else None
