"""RB7, the paper's residual latency predictor (SimNet, arXiv:2105.05821,
the predictor zoo and Table 4), written plainly. A kernel-2 stride-2 stem
convolution to C channels, then 7 residual blocks: a dense expand to 2C
with ReLU, a kernel-2 mix convolution (stride 2 in the first
`n_stride2 - 1` blocks, with an average-pool shortcut; causal stride 1
after, with an identity shortcut) with ReLU, a dense project back to C
added to the shortcut. Then two dense layers and the hybrid head. The
input is zero-padded to a multiple of 2^n_stride2 positions. Weight layout
is the one the program takes as ``params``.

`init` makes the weights from a key; `forward` is the reference forward
pass, every matrix product through the caller's `dot`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

N_FEATURES = 50


def n_stride2(p: dict) -> int:
    return min(4, p["rb_blocks"])


def seq_padded(p: dict) -> int:
    m = 1 << n_stride2(p)
    return -(-(p["ctx_len"] + 1) // m) * m


def out_dim(p: dict) -> int:
    return 3 * (p["n_classes"] + 1)


def _dense(key, d_in, d_out):
    """He-scaled weights (ReLU keeps activations O(1) layer after layer,
    so the head's logits move with the input) and small biases."""
    kw, kb = jax.random.split(key)
    w = jax.random.truncated_normal(kw, -2.0, 2.0, (d_in, d_out), jnp.float32)
    return {"w": w * math.sqrt(2.0 / d_in),
            "b": 0.02 * jax.random.normal(kb, (d_out,), jnp.float32)}


def init(key, p: dict) -> dict:
    c = p["channels"][-1]
    keys = jax.random.split(key, p["rb_blocks"] + 3)
    params = {"stem": _dense(keys[0], 2 * N_FEATURES, c)}
    for i in range(p["rb_blocks"]):
        kb = jax.random.split(keys[1 + i], 3)
        params[f"rb{i}"] = {"expand": _dense(kb[0], c, 2 * c),
                            "mix": _dense(kb[1], 4 * c, 2 * c),
                            "project": _dense(kb[2], 2 * c, c)}
    n_pos = seq_padded(p) >> n_stride2(p)
    params["fc0"] = _dense(keys[-2], n_pos * c, p["hidden"])
    params["fc1"] = _dense(keys[-1], p["hidden"], out_dim(p))
    return params


def forward(params: dict, x, dot, p: dict):
    """(B, seq_padded, 50) -> raw head outputs (B, 33)."""
    B, N, C = x.shape
    stem = params["stem"]
    h = jax.nn.relu(dot(x.reshape(B, N // 2, 2 * C), stem["w"]) + stem["b"])
    for i in range(p["rb_blocks"]):
        blk = params[f"rb{i}"]
        y = jax.nn.relu(dot(h, blk["expand"]["w"]) + blk["expand"]["b"])
        B, N, C2 = y.shape
        if i < n_stride2(p) - 1:
            pairs = y.reshape(B, N // 2, 2 * C2)
            skip = 0.5 * (h[:, 0::2] + h[:, 1::2])
        else:
            prev = jnp.concatenate([jnp.zeros_like(y[:, :1]), y[:, :-1]], axis=1)
            pairs = jnp.concatenate([prev, y], axis=-1)
            skip = h
        y = jax.nn.relu(dot(pairs, blk["mix"]["w"]) + blk["mix"]["b"])
        h = skip + (dot(y, blk["project"]["w"]) + blk["project"]["b"])
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(dot(h, params["fc0"]["w"]) + params["fc0"]["b"])
    return dot(h, params["fc1"]["w"]) + params["fc1"]["b"]


def flops_per_instruction(p: dict) -> float:
    """2 x multiply-accumulates of one forward pass (one lane, one step)."""
    c, N = p["channels"][-1], seq_padded(p)
    n = N // 2
    mac = n * 2 * N_FEATURES * c
    for i in range(p["rb_blocks"]):
        mac += n * c * 2 * c  # expand
        if i < n_stride2(p) - 1:
            n //= 2
        mac += n * (4 * c) * (2 * c)  # mix
        mac += n * 2 * c * c  # project
    mac += n * c * p["hidden"] + p["hidden"] * out_dim(p)
    return 2.0 * mac
