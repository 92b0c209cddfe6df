"""time_to_target_s: window seconds up to the end of the last episode that
reached the configuration's accuracy target, over the number of such
episodes (host clock).  Episodes that hit their cap count in ``failed``."""


def read(ctx):
    reached = ctx["window"]["reached_s"]
    return reached[-1] / len(reached) if reached else None
