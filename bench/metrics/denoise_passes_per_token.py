"""Passes a token emitted under generation by diffusion over blocks (``experimental/block_model.py``), over the window:
the ledger totals (``denoise_passes`` + ``commit_passes``) / ``tokens_emitted`` (``/debug/efficiency``, two scrapes;
counted on the device, rows x passes: ``BlockDiffusionInferenceModel.STATS``). A full block of 4 is 4 denoising passes
and a commit pass under the static rule: 1.25. A prompt's partial block takes fewer denoising passes for fewer new
tokens (3 fixed positions: 2 passes for 1 token) and ``max_tokens`` inside a block discards tokens that were denoised,
so the cell reads a little over 1.25; the dynamic rule at real confidences would read under it."""

NAME = "denoise_passes_per_token"
UNIT = "passes/token"
LAYER = "Model step (experimental/backend.py, inference_model.py)"
MOVES = "tpot_mean_ms"
SOURCE = "program_counter"


def reduce(run):
    from bench.harness.diffusion_scopes import counter_delta

    counts = [counter_delta(run, k) for k in ("denoise_passes", "commit_passes", "tokens_emitted")]
    if None in counts or not counts[2]:
        return None
    return (counts[0] + counts[1]) / counts[2]
