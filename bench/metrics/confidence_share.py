"""Device time of the serving step programs (decode and mixed) under ``confidence`` and ``unmask`` in a program that
generates by diffusion over blocks (``experimental/block_model.py``): the best token and its softmax probability at
every position of every live block (one ``argmax`` and one ``logsumexp`` a row over the vocabulary slice, the mask id's
logit left out) and the unmasking rule, over the programs' device time in the traced span. What this kind has in the
place of the sampler's sorts."""

NAME = "confidence_share"
UNIT = "%"
LAYER = "Model step (experimental/backend.py, inference_model.py)"
MOVES = "tpot_mean_ms"
SOURCE = "device_trace"


def reduce(run):
    from bench.harness.diffusion_scopes import CONFIDENCE, share

    return share(run, CONFIDENCE)
