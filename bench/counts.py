"""Operations and bytes of one scan step, computed from shapes.

The counts are the benchmark's own, so that a change to the program cannot
move the yardstick. FLOPs per instruction come from the configuration's
reference module (`flops_per_instruction`: 2 x the forward pass's
multiply-accumulates). Bytes per step are what one step of the chunk
program has to move through HBM at the least:

- each lane's chunk input for this step: feat 41 x f32, address keys
  5 x i32, labels 3 x f32, the store flag and the active flag (198 B);
- the ring state's traffic: every (lane, slot) reads and writes its
  bookkeeping (residence f32, valid / in-flight-write / store flags) and
  reads its two latency planes, and each lane writes one slot
  (feat + keys + two latencies) at the cursor;
- the predictor's weights, read once per step.
"""
from __future__ import annotations

import jax
import numpy as np

STATIC, N_KEYS = 41, 5
INPUT_BYTES_PER_LANE = STATIC * 4 + N_KEYS * 4 + 3 * 4 + 1 + 1


def ring_bytes_per_step(ctx_len: int, lanes: int) -> float:
    static = STATIC * 4 + N_KEYS * 4  # written at one slot
    lat = 2 * 4  # exec/store latencies: read in full, written at one slot
    book = 4 + 3 * 1  # residence + three flags: read and written in full
    return lanes * ctx_len * (2.0 * book + lat) + lanes * (static + lat)


def weight_bytes(model, predictor: dict) -> float:
    shapes = jax.eval_shape(lambda k: model.init(k, predictor), jax.random.PRNGKey(0))
    return float(sum(np.prod(s.shape) * s.dtype.itemsize
                     for s in jax.tree_util.tree_leaves(shapes)))


def step_counts(model, predictor: dict, ctx_len: int, lanes: int) -> dict:
    """FLOPs and bytes of one scan step over `lanes` lanes (one device)."""
    flops = lanes * model.flops_per_instruction(predictor)
    nbytes = (lanes * INPUT_BYTES_PER_LANE + ring_bytes_per_step(ctx_len, lanes)
              + weight_bytes(model, predictor))
    return {"flops": flops, "bytes": nbytes}


def least_step_seconds(counts: dict, peak: dict) -> tuple:
    """(least seconds per step, the bound that sets it)."""
    compute = counts["flops"] / peak["bf16_flops_per_s"]
    memory = counts["bytes"] / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
