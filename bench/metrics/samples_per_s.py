"""samples_per_s: local-SGD samples the window completed over its seconds
(host clock).  A round of cluster c trains its real members for ``a``
steps of ``local_batch`` samples each; padding slots do not count."""


def read(ctx):
    w = ctx["window"]
    return ctx["samples"] / w["window_s"] if w["window_s"] > 0 else None
