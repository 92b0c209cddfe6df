"""host_ms_per_segment: host time per segment in the traced window: each
``bench.segment`` span's wall time minus the device-busy time inside it."""


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    segs = red.spans.get("bench.segment", [])
    if not segs:
        return None
    host = sum(b - a for a, b in segs) * 1e-9 - red.busy_within(segs)
    return 1e3 * host / len(segs)
