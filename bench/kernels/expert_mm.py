"""Operations and bytes of the held experts' grouped products in a training step
(``latent_layers.experts_grouped``: gate, up and down of a SwiGLU expert over the rows that
chose it), from the assignments the step counted on the device. Forward and the two backward
products of each matrix count; recomputation and the tiles' padding do not.

``rows``: held assignments summed over expert layers and steps; ``layer_steps``: expert
layers x steps (how often each held stack is read)."""


def flops(config, rows):
    return rows * 3 * 2 * config["hidden_size"] * config["moe_intermediate_size"] * 3


def bytes_moved(config, rows, layer_steps, element=2):
    hidden, width, held = config["hidden_size"], config["moe_intermediate_size"], config["n_routed_experts"]
    stacks = 3 * held * hidden * width * element  # the three held stacks, as the kernels read them (bf16)
    # a pass reads its rows in and writes its rows out: x twice and the activation once in, gate, up and y out
    a_row = (3 * hidden + 3 * width) * element
    return 3 * (layer_steps * stacks + rows * a_row)  # forward, the rows' gradient, the stacks' gradient


def least_seconds(config, rows, layer_steps, peaks):
    return max(flops(config, rows) / peaks["bf16_flops"],
               bytes_moved(config, rows, layer_steps) / peaks["hbm_bytes_per_s"])
