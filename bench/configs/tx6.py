"""TX6, the predictor zoo's transformer encoder (this repository's own zoo
entry beside SimNet's, arXiv:2105.05821; its widths are the zoo's), written
plainly. The 65 positions (current instruction, then the context newest
first) are projected 50 -> d, then 6 pre-norm blocks each add
Wo . MHA(RMSNorm(h)) (4 heads of d / 4, no biases in the projections) and
FFN(RMSNorm(h)) (d -> 2d -> d, ReLU, with biases), then the mean over all
positions, a dense layer to `hidden` with ReLU and the hybrid head. RMSNorm
is x / sqrt(mean(x^2) + 1e-6) times a gain; the attention logits are
scaled by 1 / sqrt(d / 4) after the product. Weight layout is the one the
program takes as ``params``.

`init` makes the weights from a key; `forward` is the reference forward
pass, every matrix product (the two attention products batched over lanes
and heads) through the caller's `dot`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

N_FEATURES = 50
RMS_EPS = 1e-6


def seq_padded(p: dict) -> int:
    return p["ctx_len"] + 1


def out_dim(p: dict) -> int:
    return 3 * (p["n_classes"] + 1)


def _weight(key, d_in, d_out, gain):
    w = jax.random.truncated_normal(key, -2.0, 2.0, (d_in, d_out), jnp.float32)
    return w * (gain / math.sqrt(d_in))


def _dense(key, d_in, d_out, gain):
    kw, kb = jax.random.split(key)
    return {"w": _weight(kw, d_in, d_out, gain),
            "b": 0.02 * jax.random.normal(kb, (d_out,), jnp.float32)}


def init(key, p: dict) -> dict:
    """Weights scaled so that activations stay O(1) and the head's outputs
    move with the input:

    - the projections into a block (``wqkv``, ``ff1``) are fan-in scaled
      (He for ``ff1``, which feeds a ReLU), so after the RMSNorm the
      attention logits have unit variance and the FFN's hidden units are
      O(1);
    - the projections out of a block (``wo``, ``ff2``) carry a further
      1 / sqrt(2 x layers), so the 12 residual additions together add about
      as much variance as one, and the residual stream stays O(1);
    - the RMSNorm gains are 1 + 0.1 N(0, 1), so a gain that is dropped or
      misplaced shows;
    - the input projection and the head's two dense layers are He-scaled,
      and the head's biases are then set from a seeded batch of synthetic
      inputs (`_synthetic_inputs`): ``fc0``'s so that each of its units is
      centred on that batch, ``fc1``'s so that each output is. The mean
      over 65 positions, most of them empty context rows, leaves a large
      part common to every input; left in, it fixes one class of a latency
      head for every input on some seeds, and the cycles then move with
      neither the input nor the precision.
    """
    d, layers = p["tx_dim"], p["tx_layers"]
    out_gain = 1.0 / math.sqrt(2.0 * layers)
    keys = jax.random.split(key, layers + 4)
    params = {"proj": _dense(keys[0], N_FEATURES, d, math.sqrt(2.0))}
    for i in range(layers):
        kb = jax.random.split(keys[1 + i], 6)
        params[f"tx{i}"] = {
            "wqkv": _weight(kb[0], d, 3 * d, 1.0),
            "wo": _weight(kb[1], d, d, out_gain),
            "ff1": _dense(kb[2], d, 2 * d, math.sqrt(2.0)),
            "ff2": _dense(kb[3], 2 * d, d, out_gain),
            "ln1_g": 1.0 + 0.1 * jax.random.normal(kb[4], (d,), jnp.float32),
            "ln2_g": 1.0 + 0.1 * jax.random.normal(kb[5], (d,), jnp.float32),
        }
    fc0 = _weight(keys[-3], d, p["hidden"], math.sqrt(2.0))
    fc1 = _weight(keys[-2], p["hidden"], out_dim(p), math.sqrt(2.0))
    u = _exact(_pooled(params, _synthetic_inputs(keys[-1], p), _exact, p), fc0)
    b0 = -jnp.mean(u, axis=0)
    params["fc0"] = {"w": fc0, "b": b0}
    params["fc1"] = {"w": fc1, "b": -jnp.mean(_exact(jax.nn.relu(u + b0), fc1), axis=0)}
    return params


def _exact(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _synthetic_inputs(key, p: dict, n: int = 256):
    """(n, ctx_len + 1, 50) inputs shaped like the program's: a one-hot
    opcode among 13, register fields (k + 1) / 128 on 40 % of the slots,
    the other static, latency and dependence columns 0 / 1 at 15 %, the
    valid column, and 0 to 8 valid context rows (a few instructions are in
    flight at a time: the other rows of the program's input are zero)."""
    k = jax.random.split(key, 5)
    n_pos = seq_padded(p)
    x = jax.nn.one_hot(jax.random.randint(k[0], (n, n_pos), 0, 13), N_FEATURES)
    regs = (jax.random.randint(k[1], (n, n_pos, 14), 0, 128) + 1) / 128.0
    x = x.at[..., 13:27].set(regs * (jax.random.uniform(k[2], regs.shape) < 0.4))
    x = x.at[..., 27:49].set(
        (jax.random.uniform(k[3], (n, n_pos, 22)) < 0.15).astype(jnp.float32))
    x = x.at[..., 49].set(1.0)
    n_valid = jax.random.randint(k[4], (n, 1), 0, 9)
    return x * (jnp.arange(n_pos)[None] <= n_valid)[..., None]


def _rms(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + RMS_EPS) * g


def _pooled(params: dict, x, dot, p: dict):
    """The trunk: (B, ctx_len + 1, 50) -> the mean over positions (B, d)."""
    B, N, _ = x.shape
    d, H = p["tx_dim"], p["tx_heads"]
    dh = d // H
    h = dot(x, params["proj"]["w"]) + params["proj"]["b"]
    for i in range(p["tx_layers"]):
        blk = params[f"tx{i}"]
        qkv = dot(_rms(h, blk["ln1_g"]), blk["wqkv"]).reshape(B, N, 3, H, dh)
        q, k, v = (qkv[:, :, j].transpose(0, 2, 1, 3) for j in range(3))  # (B, H, N, dh)
        logits = dot(q, k.transpose(0, 1, 3, 2)) / math.sqrt(dh)  # (B, H, N, N)
        e = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
        probs = e / jnp.sum(e, axis=-1, keepdims=True)
        ctx = dot(probs, v).transpose(0, 2, 1, 3).reshape(B, N, d)
        h = h + dot(ctx, blk["wo"])
        hn = _rms(h, blk["ln2_g"])
        f = jax.nn.relu(dot(hn, blk["ff1"]["w"]) + blk["ff1"]["b"])
        h = h + (dot(f, blk["ff2"]["w"]) + blk["ff2"]["b"])
    return jnp.mean(h, axis=1)


def forward(params: dict, x, dot, p: dict):
    """(B, ctx_len + 1, 50) -> raw head outputs (B, 33)."""
    h = jax.nn.relu(dot(_pooled(params, x, dot, p), params["fc0"]["w"]) + params["fc0"]["b"])
    return dot(h, params["fc1"]["w"]) + params["fc1"]["b"]


def flops_per_instruction(p: dict) -> float:
    """2 x multiply-accumulates of one forward pass (one lane, one step)."""
    d, n = p["tx_dim"], seq_padded(p)
    block = n * 3 * d * d + 2 * n * n * d + n * d * d + n * 4 * d * d  # qkv, attn, wo, FFN
    mac = (p["tx_layers"] * block + n * N_FEATURES * d + d * p["hidden"]
           + p["hidden"] * out_dim(p))
    return 2.0 * mac


def attention_flops_per_instruction(p: dict) -> float:
    """The `attention` scope's FLOPs: QK^T and PV, 2 x n^2 x d
    multiply-accumulates each, in every layer."""
    n = seq_padded(p)
    return float(p["tx_layers"] * 2 * 2 * n * n * p["tx_dim"])


def attention_bytes_per_instruction(p: dict) -> float:
    """The `attention` scope's least HBM traffic: q, k and v read and the
    context written once, in float32, in every layer (the logits and
    probabilities need not leave the chip's fast memory)."""
    return float(p["tx_layers"] * 4 * seq_padded(p) * p["tx_dim"] * 4)
