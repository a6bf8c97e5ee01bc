"""Unsupervised Neural Quantization (UNQ) — Morozov & Babenko, CVPR 2019.

The model (paper §3.2):

  encoder ``net(x)``: MLP with M output heads mapping a descriptor
      ``x ∈ R^D`` into a product of M learned spaces (each head ``d_c``-dim).
  codebooks ``C ∈ R^{M×K×d_c}``: K codewords per learned space.
  assignment: ``p(c_mk | x) = softmax_k( <net(x)_m, c_mk> / tau_m )``  (Eq. 2)
      with learned per-codebook temperature ``tau_m``.
  bottleneck: hard Gumbel-Softmax with straight-through gradients  (Eq. 5).
  decoder ``g``: MLP reconstructing x from the SUM of selected codewords
      (the additive-quantization view; the decoder input is ``d_c``-dim,
      which matches the paper's reported model sizes: 19.8 MB @ M=8,
      30.1 MB @ M=16 — a concat decoder would grow by 2x that delta).

Everything is a plain pytree + pure functions so the model composes with
pjit/shard_map and the AOT dry-run without a module framework.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

Params = Any
State = Any


@dataclasses.dataclass(frozen=True)
class UNQConfig:
    """Hyper-parameters of the UNQ model (paper §4.1 defaults)."""

    dim: int = 96              # D: descriptor dimensionality (Deep1M: 96)
    num_codebooks: int = 8     # M: bytes per vector (K=256 -> 1 byte/codebook)
    codebook_size: int = 256   # K
    code_dim: int = 256        # d_c: dimensionality of the learned spaces
    hidden_dim: int = 1024     # two 1024-unit hidden layers (paper §4.1)
    num_hidden_layers: int = 2
    init_temperature: float = 1.0
    bn_momentum: float = 0.9
    dtype: Any = jnp.float32

    @property
    def bytes_per_vector(self) -> int:
        # K=256 -> one uint8 per codebook.
        assert self.codebook_size <= 256
        return self.num_codebooks

    def with_(self, **kw) -> "UNQConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# MLP + BatchNorm substrate (paper: Linear -> BN -> ReLU blocks)
# ---------------------------------------------------------------------------

def _init_linear(key, d_in: int, d_out: int, dtype) -> Params:
    # He/Kaiming init, suitable for the ReLU stacks used throughout the paper.
    w_key, _ = jax.random.split(key)
    scale = jnp.sqrt(2.0 / d_in)
    return {
        "w": (jax.random.normal(w_key, (d_in, d_out)) * scale).astype(dtype),
        "b": jnp.zeros((d_out,), dtype),
    }


def _init_bn(d: int, dtype) -> tuple[Params, State]:
    params = {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    state = {"mean": jnp.zeros((d,), jnp.float32), "var": jnp.ones((d,), jnp.float32)}
    return params, state


def _bn_apply(params, state, x, *, train: bool, momentum: float):
    """BatchNorm over the leading (batch) axis. Returns (y, new_state)."""
    if train:
        mean = jnp.mean(x.astype(jnp.float32), axis=0)
        var = jnp.var(x.astype(jnp.float32), axis=0)
        new_state = {
            "mean": momentum * state["mean"] + (1 - momentum) * mean,
            "var": momentum * state["var"] + (1 - momentum) * var,
        }
    else:
        mean, var = state["mean"], state["var"]
        new_state = state
    inv = jax.lax.rsqrt(var + 1e-5)
    y = (x - mean.astype(x.dtype)) * inv.astype(x.dtype)
    y = y * params["scale"] + params["bias"]
    return y, new_state


def _init_mlp(key, d_in: int, hidden: int, n_hidden: int, d_out: int, dtype):
    """Linear->BN->ReLU (x n_hidden) -> Linear head."""
    keys = jax.random.split(key, n_hidden + 1)
    layers, bn_params, bn_state = [], [], []
    d = d_in
    for i in range(n_hidden):
        layers.append(_init_linear(keys[i], d, hidden, dtype))
        p, s = _init_bn(hidden, dtype)
        bn_params.append(p)
        bn_state.append(s)
        d = hidden
    head = _init_linear(keys[-1], d, d_out, dtype)
    params = {"layers": layers, "bn": bn_params, "head": head}
    return params, {"bn": bn_state}


def _mlp_apply(params, state, x, *, train: bool, momentum: float):
    """Inference (``train=False``: the LUT-forming encoder and the d1
    decoder) runs its dots with full f32 passes, which a TPU's default
    single bf16 pass would round; training keeps the default."""
    precision = None if train else jax.lax.Precision.HIGHEST
    new_bn = []
    for lin, bn_p, bn_s in zip(params["layers"], params["bn"], state["bn"]):
        x = jnp.dot(x, lin["w"], precision=precision) + lin["b"]
        x, s = _bn_apply(bn_p, bn_s, x, train=train, momentum=momentum)
        new_bn.append(s)
        x = jax.nn.relu(x)
    x = jnp.dot(x, params["head"]["w"], precision=precision) \
        + params["head"]["b"]
    return x, {"bn": new_bn}


# ---------------------------------------------------------------------------
# UNQ model
# ---------------------------------------------------------------------------

def init(key, cfg: UNQConfig) -> tuple[Params, State]:
    """Initialize UNQ parameters and BatchNorm state."""
    k_enc, k_dec, k_cb = jax.random.split(key, 3)
    enc_params, enc_state = _init_mlp(
        k_enc, cfg.dim, cfg.hidden_dim, cfg.num_hidden_layers,
        cfg.num_codebooks * cfg.code_dim, cfg.dtype)
    dec_params, dec_state = _init_mlp(
        k_dec, cfg.code_dim, cfg.hidden_dim, cfg.num_hidden_layers,
        cfg.dim, cfg.dtype)
    codebooks = (jax.random.normal(
        k_cb, (cfg.num_codebooks, cfg.codebook_size, cfg.code_dim))
        * (1.0 / jnp.sqrt(cfg.code_dim))).astype(cfg.dtype)
    params = {
        "encoder": enc_params,
        "decoder": dec_params,
        "codebooks": codebooks,
        # tau_m in (0, inf), learned; parameterized on the log scale.
        "log_tau": jnp.full((cfg.num_codebooks,), jnp.log(cfg.init_temperature),
                            cfg.dtype),
    }
    state = {"encoder": enc_state, "decoder": dec_state}
    return params, state


def encode_heads(params, state, cfg: UNQConfig, x, *, train: bool):
    """``net(x)``: (B, D) -> (B, M, d_c) plus new BN state."""
    h, new_state = _mlp_apply(params["encoder"], state["encoder"], x,
                              train=train, momentum=cfg.bn_momentum)
    heads = h.reshape(x.shape[0], cfg.num_codebooks, cfg.code_dim)
    return heads, new_state


def head_logits(params, heads):
    """Raw dot products ``<net(x)_m, c_mk>``: (B, M, d_c) -> (B, M, K).

    Full f32 passes: these are the d2 tables and the encoder's argmax,
    which a TPU's default single bf16 pass would round."""
    return jnp.einsum("bmd,mkd->bmk", heads, params["codebooks"],
                      precision=jax.lax.Precision.HIGHEST)


def assignment_log_probs(params, heads):
    """``log p(c_mk | x)`` (Eq. 2): temperature-scaled log-softmax, (B, M, K)."""
    tau = jnp.exp(params["log_tau"])  # (M,)
    logits = head_logits(params, heads) / tau[None, :, None]
    return jax.nn.log_softmax(logits, axis=-1)


def encode(params, state, cfg: UNQConfig, x) -> jax.Array:
    """Deterministic encoder ``f(x)`` (Eq. 4): (B, D) -> uint8 codes (B, M).

    argmax over the dot products (temperature does not change the argmax).
    """
    heads, _ = encode_heads(params, state, cfg, x, train=False)
    logits = head_logits(params, heads)
    return jnp.argmax(logits, axis=-1).astype(jnp.uint8)


def gumbel_softmax_st(key, log_probs, *, hard: bool = True,
                      noise: bool = True):
    """Hard Gumbel-Softmax with straight-through gradients (Eq. 5).

    log_probs: (..., K). Returns a (soft or hard-ST) simplex vector (..., K).
    The Gumbel-Softmax temperature is fixed at 1 as in the paper.
    ``noise=False`` gives the deterministic softmax relaxation (the
    "UNQ w/o Gumbel" ablation, cf. soft-to-hard quantization [1]).
    """
    if noise:
        gumbel = -jnp.log(-jnp.log(
            jax.random.uniform(key, log_probs.shape, minval=1e-20,
                               maxval=1.0)) + 1e-20)
        logits = log_probs + gumbel.astype(log_probs.dtype)
    else:
        logits = log_probs
    y_soft = jax.nn.softmax(logits, axis=-1)
    if not hard:
        return y_soft
    idx = jnp.argmax(y_soft, axis=-1)
    y_hard = jax.nn.one_hot(idx, log_probs.shape[-1], dtype=y_soft.dtype)
    # Straight-through: forward = one-hot, backward = d(soft)/d(inputs).
    return y_hard + y_soft - jax.lax.stop_gradient(y_soft)


def decode_from_onehot(params, state, cfg: UNQConfig, onehots, *, train: bool):
    """Decoder ``g``: one-hot selections (B, M, K) -> reconstruction (B, D).

    The decoder input is the SUM over codebooks of the selected codewords
    ("the decoder adds the corresponding codewords", paper §3.2).
    """
    z = jnp.einsum("bmk,mkd->bd", onehots, params["codebooks"],
                   precision=jax.lax.Precision.HIGHEST)   # exact selection
    recon, new_state = _mlp_apply(params["decoder"], state["decoder"], z,
                                  train=train, momentum=cfg.bn_momentum)
    return recon, new_state


def decode_codes(params, state, cfg: UNQConfig, codes) -> jax.Array:
    """Decoder on integer codes (B, M) -> (B, D), eval mode (for reranking)."""
    cw = codewords_for_codes(params, codes)      # (B, M, d_c)
    z = jnp.sum(cw, axis=1)                      # (B, d_c)
    recon, _ = _mlp_apply(params["decoder"], state["decoder"], z,
                          train=False, momentum=cfg.bn_momentum)
    return recon


def codewords_for_codes(params, codes) -> jax.Array:
    """Gather selected codewords: codes (B, M) -> (B, M, d_c)."""
    cb = params["codebooks"]                      # (M, K, d_c)
    m_idx = jnp.arange(cb.shape[0])[None, :]      # (1, M)
    return cb[m_idx, codes.astype(jnp.int32)]    # (B, M, d_c)


def forward_train(key, params, state, cfg: UNQConfig, x, *, hard: bool = True,
                  gumbel_noise: bool = True):
    """One training-mode pass: returns dict with everything the losses need."""
    heads, enc_state = encode_heads(params, state, cfg, x, train=True)
    log_p = assignment_log_probs(params, heads)          # (B, M, K)
    onehots = gumbel_softmax_st(key, log_p, hard=hard,
                                noise=gumbel_noise)      # (B, M, K)
    recon, dec_state = decode_from_onehot(
        params, {**state, "encoder": enc_state}, cfg, onehots, train=True)
    new_state = {"encoder": enc_state, "decoder": dec_state}
    return {
        "heads": heads,          # net(x): (B, M, d_c)
        "log_probs": log_p,      # log p(c|x): (B, M, K)
        "onehots": onehots,      # hard-ST selections: (B, M, K)
        "recon": recon,          # g(f~(x)): (B, D)
        "state": new_state,
    }


def model_size_bytes(params) -> int:
    from repro.utils.pytree import param_bytes
    return param_bytes(params)
