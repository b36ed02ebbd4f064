"""C3, the paper's CNN latency predictor (SimNet, arXiv:2105.05821, the
predictor zoo and Table 4), written plainly: three non-overlapping kernel-2
stride-2 convolutions with ReLU over the (current + context) instruction
sequence, zero-padded to a multiple of 8 positions, then two dense layers
and the hybrid head (per latency type 10 class logits and one regression
output). Weight layout is the one the program takes as ``params``.

`init` makes the weights from a key; `forward` is the reference forward
pass, every matrix product through the caller's `dot`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

N_FEATURES = 50


def seq_padded(p: dict) -> int:
    m = 1 << len(p["channels"])
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
    chans = [N_FEATURES] + list(p["channels"])
    keys = jax.random.split(key, len(chans) + 1)
    params = {f"conv{i}": _dense(keys[i], 2 * chans[i], chans[i + 1])
              for i in range(len(chans) - 1)}
    n_pos = seq_padded(p) >> (len(chans) - 1)
    params["fc0"] = _dense(keys[-2], n_pos * chans[-1], p["hidden"])
    params["fc1"] = _dense(keys[-1], p["hidden"], out_dim(p))
    return params


def forward(params: dict, x, dot, p: dict):
    """(B, seq_padded, 50) -> raw head outputs (B, 33)."""
    h = x
    for i in range(len(p["channels"])):
        B, N, C = h.shape
        w = params[f"conv{i}"]
        h = jax.nn.relu(dot(h.reshape(B, N // 2, 2 * C), w["w"]) + w["b"])
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(dot(h, params["fc0"]["w"]) + params["fc0"]["b"])
    return dot(h, params["fc1"]["w"]) + params["fc1"]["b"]


def flops_per_instruction(p: dict) -> float:
    """2 x multiply-accumulates of one forward pass (one lane, one step)."""
    n, chans, mac = seq_padded(p), [N_FEATURES] + list(p["channels"]), 0
    for i in range(len(chans) - 1):
        n //= 2
        mac += n * 2 * chans[i] * chans[i + 1]
    mac += n * chans[-1] * p["hidden"] + p["hidden"] * out_dim(p)
    return 2.0 * mac
