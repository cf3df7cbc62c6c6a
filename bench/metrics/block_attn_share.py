"""Device time of the serving step programs (decode and mixed) under ``paged_attn`` in a program that generates by
diffusion over blocks (``experimental/block_model.py``): the ragged paged kernel walking the block table by runs under
the block mask (``block=4``), over the programs' device time in the traced span. The rest is mostly weights read once a
pass (attention projections, the held experts, the head)."""

NAME = "block_attn_share"
UNIT = "%"
LAYER = "Model step (experimental/backend.py, inference_model.py)"
MOVES = "tpot_mean_ms"
SOURCE = "device_trace"


def reduce(run):
    from bench.harness.diffusion_scopes import BLOCK_ATTN, share

    return share(run, BLOCK_ATTN)
