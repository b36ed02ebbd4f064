"""The whole step's share of the chips' peak: predictor FLOPs of every
instruction completed in the window (2 x multiply-accumulates each) over
window seconds x chips x the bf16 peak. Moves `sim_instr_per_s`."""


def read(r):
    if r.window.instructions <= 0 or r.window_s <= 0:
        return None
    flops = r.window.instructions * r.flops_per_instruction()
    return 100.0 * flops / (r.window_s * r.chips * r.peak["bf16_flops_per_s"])
