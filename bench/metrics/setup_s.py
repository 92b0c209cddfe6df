"""setup_s: process start -> the first timed segment: data, k-means, the
DQN pretrain, compiles (or compile-cache loads) and the first steps."""


def read(ctx):
    return ctx["setup_s"]
