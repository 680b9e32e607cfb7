"""Device milliseconds per train step in the outside_scan region: the first
``while`` of the step program is the forward layer scan, the longest of
the others the backward, the rest lies outside the scans."""

from benchmarks import readers


def read(spans, facts, trace, info):
    regions = readers.train_regions(trace)
    return None if regions is None else regions["outside_scan_ms"]
