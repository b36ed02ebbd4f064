"""Pallas TPU kernel: one fused SimNet sim-step inference.

The ring-buffer layout (core.simulator, ``SimConfig.layout="ring"``) keeps
the per-lane in-flight queue in HBM untouched except for one slot write per
step — which leaves the MODEL INPUT assembly as the last O(L·Q·F) HBM term:
the unfused path materializes a fresh recency-ordered (L, 1+Q, 50) tensor
every instruction just to feed the conv trunk.

This kernel removes that term. A lane-tile's ring-buffer planes are read
into VMEM ONCE; the dependency-flag compare against the current
instruction, the dynamic-feature concat, the reorder by the global head
cursor, the sequence/channel padding, and all three k2s2 conv layers of
the C3 trunk happen register/VMEM-resident. The assembled input never
touches HBM; HBM traffic is exactly the state-plane reads + one
(N/8, TB, C3) activation write per tile.

Reorder. The ring holds the window in chronological order starting at the
head cursor (slot ``head`` is the oldest entry), so one dynamic sublane
rotation by the cursor, appending the current instruction, gives the
chronological window that `cnn_trunk.conv_stack` runs on — no reverse,
which the TPU compiler does not lower. The cursor arrives as a
scalar-prefetch operand (SMEM).

The FC head + hybrid decode stay outside (tiny GEMMs on (L, hidden)) —
see `repro.core.predictor.make_fused_predict_fn`.

`interpret=True` runs the kernel body on CPU (jnp semantics), so the whole
fused path executes and is tested everywhere; the TPU target compiles the
same kernel natively.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.cnn_trunk import conv_stack, weight_specs


def _fused_step_kernel(
    head_ref,
    feat_ref, addr_ref, resid_ref, exec_ref, store_ref, valid_ref,
    curf_ref, cura_ref,
    w1, b1, w2, b2, w3, b3,
    o_ref, *, lat_scale: float, seq_padded: int,
):
    TB, Q, CF = feat_ref.shape
    c_pad = w1.shape[0] // 2  # the (pre-padded) first conv's input width

    # dynamic features + dependency flags, in physical slot order
    valid_f = valid_ref[...].astype(jnp.float32)  # (TB, Q)
    cura = cura_ref[...][:, None, :]
    dep = jnp.logical_and(addr_ref[...] == cura, cura != 0).astype(jnp.float32)
    ctx = jnp.concatenate(
        [
            feat_ref[...],
            (resid_ref[...] * lat_scale)[..., None],
            (exec_ref[...] * lat_scale)[..., None],
            (store_ref[...] * lat_scale)[..., None],
            dep,
            valid_f[..., None],
        ],
        axis=-1,
    )  # (TB, Q, 50)
    nf = ctx.shape[-1]
    ctx = ctx * valid_f[..., None]  # zero padding rows entirely
    ctx = jnp.concatenate([ctx, jnp.zeros((TB, Q, c_pad - nf), jnp.float32)], axis=-1)

    # physical → chronological: slot (head + i) mod Q holds the i-th oldest
    ctx = pltpu.roll(ctx, (Q - head_ref[0]) % Q, 1)
    ctx = jnp.swapaxes(ctx, 0, 1)  # (Q, TB, c_pad) sequence-major

    # current-instruction row: static block + zero dynamics + valid flag
    cur = jnp.concatenate(
        [
            curf_ref[...],
            jnp.zeros((TB, nf - CF - 1), jnp.float32),
            jnp.ones((TB, 1), jnp.float32),
            jnp.zeros((TB, c_pad - nf), jnp.float32),
        ],
        axis=-1,
    )  # (TB, c_pad)
    # chronological window: zero padding (older than the ring), the ring
    # oldest→newest, then the current instruction
    x = jnp.concatenate(
        [jnp.zeros((seq_padded - 1 - Q, TB, c_pad), jnp.float32), ctx, cur[None]],
        axis=0,
    )  # (seq_padded, TB, c_pad)

    h = conv_stack(
        x.reshape(seq_padded * TB, c_pad), [(w1, b1), (w2, b2), (w3, b3)],
        seq_padded, TB,
    )
    o_ref[...] = h.reshape(seq_padded // 8, TB, -1)


def fused_step_pallas(
    feat, addr, resid, exec_lat, store_lat, valid, head, cur_feat, cur_addr,
    weights, *, seq_padded: int, lane_tile: int = 64, interpret: bool = True,
):
    """feat: (B, Q, 41) f32; addr: (B, Q, 5) i32; resid/exec_lat/store_lat/
    valid: (B, Q); head: (1,) i32 global ring cursor; cur_feat: (B, 41) f32;
    cur_addr: (B, 5) i32; weights: [(w1, b1), (w2, b2), (w3, b3)] with the
    first weight's input side pre-padded to the kernel's channel pad.

    Returns (B, seq_padded//8, C3) in recency order. B must divide by
    lane_tile (ops.py pads); seq_padded by 8 (three stride-2 stages).
    """
    from repro.core.features import LAT_SCALE

    B, Q, CF = feat.shape
    assert len(weights) == 3, "fused_step fuses exactly the C3 depth"
    assert seq_padded % 8 == 0 and seq_padded >= 1 + Q, (seq_padded, Q)
    c3 = weights[2][0].shape[1]
    TB = min(lane_tile, B)
    assert B % TB == 0, (B, TB)
    lane2 = lambda shape: pl.BlockSpec(shape, lambda i, head: (i, 0))
    lane3 = lambda shape: pl.BlockSpec(shape, lambda i, head: (i, 0, 0))
    in_specs = [
        lane3((TB, Q, CF)),                    # feat
        lane3((TB, Q, addr.shape[2])),         # addr
        lane2((TB, Q)), lane2((TB, Q)), lane2((TB, Q)),  # resid/exec/store
        lane2((TB, Q)),                        # valid
        lane2((TB, CF)),                       # cur_feat
        lane2((TB, cur_addr.shape[1])),        # cur_addr
    ]
    flat, w_specs = weight_specs(weights, lambda i, head: (0, 0))
    kernel = functools.partial(
        _fused_step_kernel, lat_scale=LAT_SCALE, seq_padded=seq_padded
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B // TB,),
            in_specs=in_specs + w_specs,
            out_specs=pl.BlockSpec(
                (seq_padded // 8, TB, c3), lambda i, head: (0, i, 0)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((seq_padded // 8, B, c3), jnp.float32),
        interpret=interpret,
    )(head, feat, addr, resid, exec_lat, store_lat, valid, cur_feat, cur_addr,
      *flat)
    return jnp.flip(out, 0).transpose(1, 0, 2)
