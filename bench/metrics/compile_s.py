"""compile_s: the engine's ``compile`` spans (`repro.obs`), summed over
set-up.  With a warm persistent cache this is the cache load."""


def read(ctx):
    return ctx["compile_s"] if ctx["compile_s"] > 0 else None
