"""segment_ms_p95: 95th percentile, over every segment of the window, of
dispatch -> the segment's records and its evaluation on the host, host
clock."""
import math


def p95(values):
    s = sorted(values)
    return s[max(0, math.ceil(0.95 * len(s)) - 1)]


def read(ctx):
    seg = ctx["window"]["segments_s"]
    return 1e3 * p95(seg) if seg else None
