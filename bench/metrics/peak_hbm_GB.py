"""Peak device memory after the window: the device allocator's
``peak_bytes_in_use`` (the counter the profiler's MemoryAllocation events
carry), the largest over the cell's chips, in GB (1e9 bytes)."""


def read(run):
    peak = max(run.peak_bytes, default=0)
    return peak / 1e9 if peak > 0 else None
