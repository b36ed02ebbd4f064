"""The attention core's share of its roofline: its least time per scan
step (the larger of lanes x `attention_flops_per_instruction` over the bf16
peak and lanes x `attention_bytes_per_instruction` over the HBM bandwidth,
both from the configuration's module) over its measured device time per
step (`run_chunk.attention_us_per_step.sweep`), per device. Moves
`sim_instr_per_s`.

The bytes are a floor only for an attention that reads q, k and v and
writes its context through HBM in float32, as the scope's bounds in the
program do; a kernel that keeps them on the chip (fusing the projection or
`wo`) or stores them narrower can beat it and read above 100 %. Such a
kernel needs the floor moved to the FLOPs alone first."""


def read(r):
    from bench import manifest

    model, p = r.cell.model, r.cell.sizes["predictor"]
    if not hasattr(model, "attention_flops_per_instruction") or not r.window.batches:
        return None
    lanes = {b.n_lanes for b in r.window.batches}
    if len(lanes) != 1:
        return None  # one shape per window, or no single roofline
    measured_us = manifest.reader("run_chunk.attention_us_per_step.sweep").read(r)
    if measured_us is None:
        return None
    per_device = lanes.pop() // r.chips
    least_s = max(per_device * model.attention_flops_per_instruction(p) / r.peak["bf16_flops_per_s"],
                  per_device * model.attention_bytes_per_instruction(p) / r.peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (measured_us * 1e-6)
