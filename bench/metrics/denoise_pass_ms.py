"""Device time of one pass (a decode sub-step: every live slot's block of 4 positions through all layers, the
confidences and the unmasking rule) under generation by diffusion over blocks: the device time of the decode program's
operations in its runs inside the decode launches of the traced span, over the passes those launches made (their
``steps`` args: ``decode_steps`` a launch). Times 1.25 passes a token over the live slots it is the device's part of
``tpot_mean_ms``."""

NAME = "denoise_pass_ms"
UNIT = "ms"
LAYER = "Model step (experimental/backend.py, inference_model.py)"
MOVES = "tpot_mean_ms"
SOURCE = "device_trace"


def reduce(run):
    from bench.harness.diffusion_scopes import table

    t = table(run)
    if not t or not t["decode"] or not t["decode"]["steps"] or not t["decode"]["program_ns"]:
        return None
    return t["decode"]["program_ns"] / t["decode"]["steps"] / 1e6
