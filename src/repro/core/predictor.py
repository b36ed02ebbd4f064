"""SimNet latency-predictor model zoo (paper §2.3, Table 4).

Models over input (B, N, 50) with N = 1 + ctx_len (current + context):

  fc2/fc3   flattened MLPs (the paper's weak baselines)
  c1/c3     1-D CNNs: kernel=2 stride=2, non-overlapping hierarchical
            convolutions (the paper's design principles), + 2 FC layers
  rb7       7 residual blocks (EfficientNet-flavoured), the accuracy champion
  lstm2     2-layer LSTM over the instruction sequence
  tx6       6-layer transformer encoder
  ithemal_lstm2  the Ithemal-style baseline: same LSTM, but the *simulator*
            feeds a fixed window of previous instructions instead of managed
            context (see core.api.ithemal_trace_arrays)

Output heads: hybrid = per-latency 10-way classification (cycles 0..8 +
overflow) + regression fallback (paper §2.3 "From Output to Latency");
reg = regression only.

The conv trunk is expressed as reshape+matmul (non-overlapping k2s2 == a
blocked GEMM) — the exact computation `repro.kernels.cnn_trunk` implements
as a fused Pallas kernel.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.features import N_FEATURES
from repro.nn.init import ShardSpec, dense_init, split_keys

N_HEADS = 3  # fetch, execution, store
REG_SCALE = 1.0 / 64.0  # regression head works in scaled-cycle space


@dataclasses.dataclass(frozen=True)
class PredictorConfig:
    kind: str = "c3"
    ctx_len: int = 64
    n_classes: int = 10
    output: str = "hybrid"  # hybrid | reg
    channels: Tuple[int, ...] = (64, 128, 128)  # conv channels (c*/rb*)
    hidden: int = 256  # FC head width
    lstm_hidden: int = 128
    tx_dim: int = 64
    tx_heads: int = 4
    tx_layers: int = 6
    rb_blocks: int = 7
    compute_dtype: str = "float32"  # "bfloat16": halve trunk activation
    # traffic (c1/c3 path; heads stay fp32 — hybrid decode is exact)

    @property
    def seq_in(self) -> int:
        return self.ctx_len + 1

    @property
    def n_stride2(self) -> int:
        if self.kind.startswith("c"):
            return len(self.channels[: int(self.kind[1])])
        if self.kind.startswith("rb"):
            return min(4, self.rb_blocks)
        return 0

    @property
    def seq_padded(self) -> int:
        m = 1 << max(self.n_stride2, 0)
        return ((self.seq_in + m - 1) // m) * m

    @property
    def out_dim(self) -> int:
        if self.output == "hybrid":
            return N_HEADS * (self.n_classes + 1)
        return N_HEADS


def _head_dims(cfg):
    return cfg.n_classes + 1 if cfg.output == "hybrid" else 1


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _conv_layer_params(key, c_in, c_out):
    """k2s2 conv as a (2*c_in, c_out) matmul weight + bias."""
    w, _ = dense_init(key, 2 * c_in, c_out, axes=(None, None))
    b = jnp.zeros((c_out,), jnp.float32)
    return {"w": w, "b": b}, {"w": ShardSpec((None, None)), "b": ShardSpec((None,))}


def _dense_params(key, d_in, d_out):
    w, _ = dense_init(key, d_in, d_out, axes=(None, None))
    b = jnp.zeros((d_out,), jnp.float32)
    return {"w": w, "b": b}, {"w": ShardSpec((None, None)), "b": ShardSpec((None,))}


def init_predictor(key, cfg: PredictorConfig):
    keys = split_keys(key, 32)
    p, s = {}, {}
    kind = cfg.kind
    if kind in ("fc2", "fc3"):
        depth = int(kind[2])
        d = cfg.seq_in * N_FEATURES
        dims = [d] + [cfg.hidden * 2] * (depth - 1) + [cfg.out_dim]
        for i in range(depth):
            p[f"fc{i}"], s[f"fc{i}"] = _dense_params(keys[i], dims[i], dims[i + 1])
    elif kind in ("c1", "c3"):
        depth = int(kind[1])
        chans = [N_FEATURES] + list(cfg.channels[:depth])
        for i in range(depth):
            p[f"conv{i}"], s[f"conv{i}"] = _conv_layer_params(keys[i], chans[i], chans[i + 1])
        n_pos = cfg.seq_padded >> depth
        p["fc0"], s["fc0"] = _dense_params(keys[depth], n_pos * chans[-1], cfg.hidden)
        p["fc1"], s["fc1"] = _dense_params(keys[depth + 1], cfg.hidden, cfg.out_dim)
    elif kind.startswith("rb"):
        n = cfg.rb_blocks
        c = cfg.channels[-1]
        p["stem"], s["stem"] = _conv_layer_params(keys[0], N_FEATURES, c)  # k2s2 stem
        for i in range(n):
            kb = split_keys(keys[1 + i], 3)
            blk, blk_s = {}, {}
            blk["expand"], blk_s["expand"] = _dense_params(kb[0], c, 2 * c)
            blk["mix"], blk_s["mix"] = _conv_layer_params(kb[1], 2 * c, 2 * c)
            blk["project"], blk_s["project"] = _dense_params(kb[2], 2 * c, c)
            p[f"rb{i}"], s[f"rb{i}"] = blk, blk_s
        n_pos = cfg.seq_padded >> cfg.n_stride2
        p["fc0"], s["fc0"] = _dense_params(keys[20], n_pos * c, cfg.hidden)
        p["fc1"], s["fc1"] = _dense_params(keys[21], cfg.hidden, cfg.out_dim)
    elif kind in ("lstm2", "ithemal_lstm2"):
        h = cfg.lstm_hidden
        dims = [N_FEATURES, h]
        for l in range(2):
            p[f"lstm{l}"], s[f"lstm{l}"] = {}, {}
            p[f"lstm{l}"]["wx"], s[f"lstm{l}"]["wx"] = dense_init(
                split_keys(keys[l], 2)[0], dims[l], 4 * h, axes=(None, None)
            )
            p[f"lstm{l}"]["wh"], s[f"lstm{l}"]["wh"] = dense_init(
                split_keys(keys[l], 2)[1], h, 4 * h, axes=(None, None)
            )
            p[f"lstm{l}"]["b"] = jnp.zeros((4 * h,), jnp.float32)
            s[f"lstm{l}"]["b"] = ShardSpec((None,))
        p["fc0"], s["fc0"] = _dense_params(keys[4], h, cfg.hidden)
        p["fc1"], s["fc1"] = _dense_params(keys[5], cfg.hidden, cfg.out_dim)
    elif kind == "tx6":
        d = cfg.tx_dim
        p["proj"], s["proj"] = _dense_params(keys[0], N_FEATURES, d)
        for l in range(cfg.tx_layers):
            kb = split_keys(keys[1 + l], 4)
            blk, bs = {}, {}
            blk["wqkv"], bs["wqkv"] = dense_init(kb[0], d, 3 * d, axes=(None, None))
            blk["wo"], bs["wo"] = dense_init(kb[1], d, d, axes=(None, None))
            blk["ff1"], bs["ff1"] = _dense_params(kb[2], d, 2 * d)
            blk["ff2"], bs["ff2"] = _dense_params(kb[3], 2 * d, d)
            blk["ln1_g"] = jnp.ones((d,), jnp.float32)
            bs["ln1_g"] = ShardSpec((None,))
            blk["ln2_g"] = jnp.ones((d,), jnp.float32)
            bs["ln2_g"] = ShardSpec((None,))
            p[f"tx{l}"], s[f"tx{l}"] = blk, bs
        p["fc0"], s["fc0"] = _dense_params(keys[20], d, cfg.hidden)
        p["fc1"], s["fc1"] = _dense_params(keys[21], cfg.hidden, cfg.out_dim)
    else:
        raise ValueError(kind)
    return p, s


# ---------------------------------------------------------------------------
# apply
# ---------------------------------------------------------------------------

def _pad_seq(x, cfg):
    pad = cfg.seq_padded - x.shape[1]
    if pad > 0:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x


def conv2s(params, x):
    """Non-overlapping k2s2 conv + bias + ReLU as reshaped matmul.
    x: (B, N, C) -> (B, N/2, C_out)."""
    B, N, C = x.shape
    xr = x.reshape(B, N // 2, 2 * C)
    return jax.nn.relu(xr @ params["w"] + params["b"])


def _dense(params, x, act=None):
    y = x @ params["w"] + params["b"]
    return jax.nn.relu(y) if act == "relu" else y


def _rms(x, g):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + 1e-6) * g


def apply_trunk(params, x, cfg: PredictorConfig, use_kernel: bool = False):
    """(B, N, 50) -> (B, hidden) features before the output head."""
    kind = cfg.kind
    x = x.astype(jnp.dtype(cfg.compute_dtype))
    if kind not in ("c1", "c3"):
        x = x.astype(jnp.float32)  # bf16 path implemented for the CNN trunk
    if kind in ("fc2", "fc3"):
        depth = int(kind[2])
        h = x.reshape(x.shape[0], -1)
        for i in range(depth - 1):
            h = _dense(params[f"fc{i}"], h, act="relu")
        return h, params[f"fc{depth-1}"]
    if kind in ("c1", "c3"):
        depth = int(kind[1])
        cdt = jnp.dtype(cfg.compute_dtype)
        h = _pad_seq(x, cfg).astype(cdt)
        if use_kernel:
            from repro.kernels import ops as kops

            h = kops.cnn_trunk([params[f"conv{i}"] for i in range(depth)], h)
        else:
            for i in range(depth):
                p = {"w": params[f"conv{i}"]["w"].astype(cdt), "b": params[f"conv{i}"]["b"].astype(cdt)}
                h = conv2s(p, h)
        h = h.reshape(h.shape[0], -1).astype(jnp.float32)
        h = _dense(params["fc0"], h, act="relu")
        return h, params["fc1"]
    if kind.startswith("rb"):
        h = conv2s(params["stem"], _pad_seq(x, cfg))
        for i in range(cfg.rb_blocks):
            blk = params[f"rb{i}"]
            stride2 = i < (cfg.n_stride2 - 1)  # static structure (stem did one)
            y = _dense(blk["expand"], h, act="relu")
            if stride2:
                B, N, C = y.shape
                y = jax.nn.relu(y.reshape(B, N // 2, 2 * C) @ blk["mix"]["w"] + blk["mix"]["b"])
                skip = 0.5 * (h[:, 0::2] + h[:, 1::2])  # avg-pool shortcut
            else:
                B, N, C = y.shape
                yp = jnp.pad(y, ((0, 0), (1, 0), (0, 0)))  # causal k2 s1
                y2 = jnp.concatenate([yp[:, :-1], y], axis=-1)
                y = jax.nn.relu(y2 @ blk["mix"]["w"] + blk["mix"]["b"])
                skip = h
            h = skip + _dense(blk["project"], y)
        h = h.reshape(h.shape[0], -1)
        h = _dense(params["fc0"], h, act="relu")
        return h, params["fc1"]
    if kind in ("lstm2", "ithemal_lstm2"):
        hdim = cfg.lstm_hidden
        B = x.shape[0]
        # feed most-recent-last so the final hidden state sees the newest
        seq = jnp.flip(x, axis=1)

        def make_cell(lp):
            def cell(carry, x_t):
                h, c = carry
                z = x_t @ lp["wx"] + h @ lp["wh"] + lp["b"]
                i, f, g, o = jnp.split(z, 4, axis=-1)
                c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
                h = jax.nn.sigmoid(o) * jnp.tanh(c)
                return (h, c), h

            return cell

        hseq = jnp.swapaxes(seq, 0, 1)  # (N, B, F)
        for l in range(2):
            init = (jnp.zeros((B, hdim)), jnp.zeros((B, hdim)))
            (_, _), hseq = jax.lax.scan(make_cell(params[f"lstm{l}"]), init, hseq)
        h = hseq[-1]
        h = _dense(params["fc0"], h, act="relu")
        return h, params["fc1"]
    if kind == "tx6":
        d, nh = cfg.tx_dim, cfg.tx_heads
        h = _dense(params["proj"], x)
        B, N, _ = h.shape
        for l in range(cfg.tx_layers):
            blk = params[f"tx{l}"]
            hn = _rms(h, blk["ln1_g"])
            qkv = hn @ blk["wqkv"]
            q, k, v = jnp.split(qkv.reshape(B, N, 3, nh, d // nh), 3, axis=2)
            q, k, v = q[:, :, 0], k[:, :, 0], v[:, :, 0]
            with jax.named_scope("attention"):
                logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(d / nh)
                probs = jax.nn.softmax(logits, axis=-1)
                ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, N, d)
            h = h + ctx @ blk["wo"]
            hn = _rms(h, blk["ln2_g"])
            h = h + _dense(blk["ff2"], jax.nn.relu(_dense(blk["ff1"], hn)))
        h = jnp.mean(h, axis=1)
        h = _dense(params["fc0"], h, act="relu")
        return h, params["fc1"]
    raise ValueError(kind)


# repro-lint: scan-reachable — called from the sim-step under lax.scan
def apply_raw(params, x, cfg: PredictorConfig, use_kernel: bool = False):
    """(B, N, 50) -> raw head outputs (B, out_dim)."""
    with jax.named_scope("trunk"):
        h, head = apply_trunk(params, x, cfg, use_kernel=use_kernel)
    with jax.named_scope("head"):
        return _dense(head, h)


def split_heads(raw, cfg: PredictorConfig):
    """-> (cls_logits (B, 3, n_classes) or None, reg (B, 3))."""
    B = raw.shape[0]
    if cfg.output == "hybrid":
        r = raw.reshape(B, N_HEADS, cfg.n_classes + 1)
        return r[..., : cfg.n_classes], r[..., cfg.n_classes]
    return None, raw


# repro-lint: scan-reachable — called from the sim-step under lax.scan
def decode_latency(raw, cfg: PredictorConfig):
    """Hybrid decode (paper §2.3): argmax class if < overflow else regression.
    Returns (B, 3) float latencies (regression head is in REG_SCALE space)."""
    with jax.named_scope("head"):
        cls_logits, reg = split_heads(raw, cfg)
        reg = jax.nn.relu(reg) / REG_SCALE
        if cls_logits is None:
            return reg
        cls = jnp.argmax(cls_logits, axis=-1)
        overflow = cls == (cfg.n_classes - 1)
        return jnp.where(overflow, jnp.maximum(reg, float(cfg.n_classes - 1)),
                         cls.astype(jnp.float32))


def make_predict_fn(params, cfg: PredictorConfig, use_kernel: bool = False):
    def predict(x):
        raw = apply_raw(params, x, cfg, use_kernel=use_kernel)
        return decode_latency(raw, cfg)

    return predict


def make_fused_predict_fn(params, cfg: PredictorConfig):
    """Fused ring-state predictor (kernels/fused_step.py): model-input
    assembly + the C3 conv trunk run in ONE Pallas kernel straight off the
    ring-buffer SimState — the (L, 1+Q, 50) input tensor never reaches
    HBM. The FC head + hybrid decode stay in jnp (tiny GEMMs).

    Signature matches `make_sim_scan`'s ``predict_state_fn``:
    (state, cur_feat, cur_addr) -> (L, 3) latencies. Requires the ring
    layout (the kernel reads the global head cursor), kind == "c3" (the
    kernel fuses exactly that conv depth), and an f32 state: the kernel
    assembles in f32, so a bf16 ``state_dtype`` would skip the unfused
    path's bf16 rounding of the dynamic features (the engine gates on
    this and falls back to the unfused kernel for bf16 state).
    """
    if cfg.kind != "c3":
        raise ValueError(
            f"fused_step fuses the C3 trunk; got kind={cfg.kind!r} "
            "(use the unfused use_kernel path for other models)"
        )
    from repro.kernels import ops as kops

    conv = [params[f"conv{i}"] for i in range(3)]

    def predict(state, cur_feat, cur_addr):
        h = kops.fused_step(
            conv, state, cur_feat, cur_addr, seq_padded=cfg.seq_padded
        )
        h = h.reshape(h.shape[0], -1).astype(jnp.float32)
        h = _dense(params["fc0"], h, act="relu")
        with jax.named_scope("head"):
            raw = _dense(params["fc1"], h)
        return decode_latency(raw, cfg)

    return predict


# ---------------------------------------------------------------------------
# computation intensity (Table 4's "MFlops per inference")
# ---------------------------------------------------------------------------

def inference_mflops(cfg: PredictorConfig) -> float:
    N, Fdim = cfg.seq_padded, N_FEATURES
    total = 0.0
    kind = cfg.kind
    if kind in ("fc2", "fc3"):
        depth = int(kind[2])
        dims = [cfg.seq_in * Fdim] + [cfg.hidden * 2] * (depth - 1) + [cfg.out_dim]
        for i in range(depth):
            total += dims[i] * dims[i + 1]
    elif kind in ("c1", "c3"):
        depth = int(kind[1])
        chans = [Fdim] + list(cfg.channels[:depth])
        n = N
        for i in range(depth):
            n //= 2
            total += n * 2 * chans[i] * chans[i + 1]
        total += (n * chans[-1]) * cfg.hidden + cfg.hidden * cfg.out_dim
    elif kind.startswith("rb"):
        c = cfg.channels[-1]
        n = N // 2
        total += (N // 2) * 2 * Fdim * c
        for i in range(cfg.rb_blocks):
            stride2 = i < cfg.n_stride2 - 1
            total += n * c * 2 * c  # expand
            if stride2:
                total += (n // 2) * (4 * c) * (2 * c)
                n //= 2
            else:
                total += n * (4 * c) * (2 * c)
            total += n * 2 * c * c  # project
        total += n * c * cfg.hidden + cfg.hidden * cfg.out_dim
    elif kind in ("lstm2", "ithemal_lstm2"):
        h = cfg.lstm_hidden
        total += cfg.seq_in * (Fdim * 4 * h + h * 4 * h)
        total += cfg.seq_in * (h * 4 * h + h * 4 * h)
        total += h * cfg.hidden + cfg.hidden * cfg.out_dim
    elif kind == "tx6":
        d = cfg.tx_dim
        n = cfg.seq_in
        per = n * (3 * d * d) + 2 * n * n * d + n * d * d + n * (4 * d * d)
        total += cfg.tx_layers * per + Fdim * d * n + d * cfg.hidden + cfg.hidden * cfg.out_dim
    return total / 1e6
