"""fleet_mfu: the whole fleet step's model FLOPs over the traced window's
seconds over the chip's bf16 peak, in percent.

Model FLOPs, real members only (padding and recomputation do not count),
with N_mm = dim*hidden + hidden*classes the MLP's multiply-accumulates per
sample: 6*N_mm per trained sample-step, 2*N_mm per sample whose
post-training loss the round takes, 2*N_mm per evaluated sample.
"""


def n_mm(dims: dict) -> int:
    return dims["dim"] * dims["hidden"] + dims["hidden"] * dims["n_classes"]


def round_flops(a: int, members: int, local_batch: int, dims: dict) -> int:
    """One cluster round: ``a`` SGD steps and one loss per member."""
    return (6 * a + 2) * members * local_batch * n_mm(dims)


def eval_flops(n_eval: int, dims: dict) -> int:
    return 2 * n_eval * n_mm(dims)


def model_flops(rounds, members, local_batch, evals, n_eval, dims) -> int:
    return (sum(round_flops(a, int(members[c]), local_batch, dims)
                for c, a in rounds) + evals * eval_flops(n_eval, dims))


def read(ctx):
    red = ctx["trace"]
    if red is None or red.window_s <= 0:
        return None
    w = ctx["window"]
    # an evaluation scores every sample for accuracy and 1024 for the loss
    n_eval = ctx["cfg"]["data"]["n_samples"] + 1024
    flops = model_flops(w["rounds"], ctx["members"],
                        ctx["spec"]["local_batch"], w["evals"], n_eval,
                        ctx["dims"])
    return 100.0 * flops / red.window_s / ctx["peaks"]["bf16_flops"]
