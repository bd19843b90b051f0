"""ops/ kernels, taken over the whole query so that it reads the same work
whatever implements it: the least time the chip's HBM could take to stream
the bytes the query must touch (``min_bytes`` beside the query, over the peak
of ``peaks.json``), as a share of the device-busy time per query in the
traced part. Bound by bytes: these queries do a few operations per byte."""


def read(run):
    t, done = run.trace, run.traced_requests
    gbps = run.peaks.get("hbm_gbps")
    if not t or not t.get("busy_s") or not done or not gbps:
        return None
    least_s = sum(run.min_bytes[key] for _, _, _, key in done) / (gbps * 1e9)
    return 100.0 * least_s / t["busy_s"]
