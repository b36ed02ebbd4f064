"""Pallas TPU kernel: the ENTIRE SimNet C3 trunk fused in one kernel.

Beyond-paper optimization (DESIGN.md §6): at inference the C3 model is a
chain of tiny GEMMs — on GPU (the paper's TensorRT path) each layer pays a
kernel launch and an HBM round-trip, which dominates for small models.
Here a lane-tile's activations stay VMEM-resident through all three conv
layers: HBM traffic is exactly one input read + one output write per tile.

Layout. Inside the kernel the window is SEQUENCE-major, (N, TB, C): the
sequence position is the leading axis, the lanes of a tile fill the
sublanes and the channels the vector lanes. A k2s2 conv then pairs
positions by splitting the leading axis, and (N/2, TB, C) -> (N/2·TB, C)
is a free reshape because TB is a multiple of 8. (The model's own
(TB, N, C) -> (TB·N/2, 2C) reshape folds sublanes into lanes, which the
TPU compiler refuses.)

Order. The kernels run the window in CHRONOLOGICAL order (oldest first,
the current instruction last), the reverse of the model's recency order
(current first). Reversing an even-length sequence keeps the k2s2 pairs
together with their two halves swapped, so each layer multiplies the
even position by the weight's second half and the odd one by its first,
and its outputs come out reversed too: the caller flips the N/8 output
positions back. The fused sim-step kernel needs this order because the
ring buffer holds it natively (oldest at the head cursor) and the TPU
compiler has no in-kernel reverse.

All intermediate buffers live in kernel registers/VMEM; weights are tiny
(≤ 128 KiB total) and replicated into VMEM once per tile.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def conv_stack(h, weights, n: int, tb: int):
    """Three k2s2 conv + bias + ReLU layers over a chronological window.

    h: (n·tb, C) — sequence-major rows, oldest position first; weights:
    [(w_ref, b_ref)] with w (2C, Co) in the model's (recency-pair) order,
    b (1, Co). Returns (n/8·tb, C3), also chronological."""
    for w_ref, b_ref in weights:
        c = h.shape[-1]
        pairs = h.reshape(n // 2, 2, tb, c)
        older = pairs[:, 0].reshape((n // 2) * tb, c)
        newer = pairs[:, 1].reshape((n // 2) * tb, c)
        w = w_ref[...]
        # recency pair (newer, older) meets the weight halves (w[:c], w[c:])
        y = (jnp.dot(newer, w[:c], preferred_element_type=jnp.float32)
             + jnp.dot(older, w[c:], preferred_element_type=jnp.float32))
        h = jax.nn.relu(y + b_ref[...])
        n //= 2
    return h


def weight_specs(weights, index_map):
    """Flat operands + whole-array BlockSpecs for [(w, b)] conv weights
    (biases as (1, Co) rows)."""
    flat, specs = [], []
    for w, b in weights:
        b = b.reshape(1, -1)
        flat += [w, b]
        specs += [pl.BlockSpec(w.shape, index_map),
                  pl.BlockSpec(b.shape, index_map)]
    return flat, specs


def _trunk_kernel(x_ref, w1, b1, w2, b2, w3, b3, o_ref):
    n, tb, c = x_ref.shape
    h = conv_stack(x_ref[...].reshape(n * tb, c), [(w1, b1), (w2, b2), (w3, b3)], n, tb)
    o_ref[...] = h.reshape(n // 8, tb, -1)


def cnn_trunk_pallas(x, weights, *, lane_tile: int = 64, interpret: bool = True):
    """x: (B, N, C) in recency order; weights: [(w1,b1),(w2,b2),(w3,b3)]
    with wi: (2Ci, Ci+1).

    Returns (B, N//8, C3). N must be divisible by 8; B by lane_tile
    (ops.py pads both)."""
    B, N, C = x.shape
    assert len(weights) == 3, "cnn_trunk fuses exactly the C3 depth"
    c3 = weights[-1][0].shape[1]
    TB = min(lane_tile, B)
    assert B % TB == 0 and N % 8 == 0, (B, N)
    flat, w_specs = weight_specs(weights, lambda i: (0, 0))
    out = pl.pallas_call(
        _trunk_kernel,
        grid=(B // TB,),
        in_specs=[pl.BlockSpec((N, TB, C), lambda i: (0, i, 0))] + w_specs,
        out_specs=pl.BlockSpec((N // 8, TB, c3), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((N // 8, B, c3), jnp.float32),
        interpret=interpret,
    )(jnp.flip(x, 1).transpose(1, 0, 2), *flat)
    return jnp.flip(out, 0).transpose(1, 0, 2)
