"""Plain float32 reference forward pass of DeepSeek-V2 (family ``moe``:
multi-head latent attention, a leading dense layer, then routed and
shared experts), as one device's share of expert parallelism.

Straightforward ``jax.numpy`` at ``jax.default_matmul_precision("highest")``:
no cache, no batching, no kernels, one sequence at a time, one layer per
call.  It imports nothing of the program and reads only the parameter
tree the benchmark made (``bench/weights.py``), by leaf name.  Every
size and setting comes from ``dims``, the configuration file's ``model``
section.

The equations (DeepSeek-V2, arXiv:2405.04434, and its
``modeling_deepseek.py``), per token sequence of length T:

* Attention, in its published non-absorbed form: ``q = x @ wq`` split
  per head into ``q_nope`` (``qk_nope_dim``) and ``q_pe``
  (``qk_rope_dim``); ``[c, k_pe] = x @ w_dkv``, ``c = rmsnorm(c) *
  kv_norm``; per-head ``k_nope = c @ w_uk`` and ``v = c @ w_uv``; rope
  on ``q_pe`` and on the one ``k_pe`` all heads share; causal softmax of
  ``[q_nope, q_pe] . [k_nope, k_pe]`` scaled by ``(qk_nope_dim +
  qk_rope_dim) ** -0.5 * yarn_get_mscale(factor, mscale_all_dim) ** 2``;
  ``h += (p @ v) @ wo``.  The program caches ``c`` and ``k_pe`` and
  scores in the absorbed form; this form checks it independently.
* Rope: YaRN on interleaved pairs ``(x[2i], x[2i+1])``: inverse
  frequencies ``f_i = theta ** (-2i / d)`` become ``f_i / factor * ramp_i
  + f_i * (1 - ramp_i)``, with ``ramp`` rising linearly from 0 to 1
  between the dimensions at which ``beta_fast`` and ``beta_slow``
  rotations fit in ``original_max_position_embeddings`` positions;
  cos and sin are scaled by ``mscale(factor, mscale) / mscale(factor,
  mscale_all_dim)``.
* FFN of the first ``first_dense`` layers: ``w_down(silu(w_gate x) *
  w_up x)`` of width ``dense_ff``.
* MoE layers: softmax over ``router_width`` router logits, greedy top
  ``top_k``, probabilities not renormalised; of the routed experts this
  device holds ``num_experts``, from ``first_held`` on, and a token adds
  ``p * expert(x)`` for each of its top-k that is held, with no capacity;
  the ``num_shared`` shared experts (one SwiGLU of ``num_shared *
  d_expert``) see every token.
* Logits: ``rmsnorm(h) * ln_f @ lm_head``.

Departures from the published model, all of them the deployment's and
shared with the program: the routed experts that other chips hold add
nothing, and the weights are random.

Two weight sets: positions before ``split`` (the prompt) use the
weights as stored, the rest (decoded tokens) use every matrix named in
``decode_nf4`` quantized to NF4 by this module (``nf4.py``), each routed
expert's matrix on its own.  ``low_precision=True`` is the control:
every matrix product's operands are rounded to float8 e4m3 (per-tensor
absmax scaling) before an exact product.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from reference.nf4 import nf4_dequant

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _fp8(x):
    """Round to float8 e4m3 with one absmax scale for the whole tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(x, w, low):
    if low:
        x, w = _fp8(x), _fp8(w)
    return jnp.dot(x, w, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _pick(y, y_dec, split):
    """``y`` at positions before ``split``, ``y_dec`` after."""
    pre = (jnp.arange(y.shape[0]) < split)[:, None]
    return jnp.where(pre, y, y_dec)


def _proj(x, w, w_dec, split, low):
    """``x @ w`` at positions before ``split``, ``x @ w_dec`` after."""
    y = _mm(x, w, low)
    return y if w_dec is None else _pick(y, _mm(x, w_dec, low), split)


def _weights(tree, names, quant):
    """Leaves ``names`` in f32, and their NF4 decode copies or None."""
    w = {k: tree[k].astype(F32) for k in names}
    dec = {}
    for k in names:
        if k not in quant:
            dec[k] = None
        elif w[k].ndim == 3:                        # stacked routed experts
            dec[k] = jax.vmap(nf4_dequant)(w[k])
        else:
            dec[k] = nf4_dequant(w[k])
    return w, dec


def _mscale(scale, m):
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def yarn_inv_freq(dims: dict) -> np.ndarray:
    """Rope inverse frequencies of the ``qk_rope_dim`` dimensions."""
    d = dims["mla"]["qk_rope_dim"]
    theta = dims["rope_theta"]
    base = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    rs = dims.get("rope_scaling")
    if rs is None:
        return base

    def dim_of(rotations):
        n = rs["original_max_position_embeddings"]
        return d * math.log(n / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim_of(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rs["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return base / rs["factor"] * ramp + base * (1.0 - ramp)


def _rope(x, inv_freq, cs_scale):
    """Rotate interleaved pairs of (T, ..., d) by position."""
    t = x.shape[0]
    ang = jnp.arange(t, dtype=F32)[:, None] * jnp.asarray(inv_freq, F32)
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(ang) * cs_scale, jnp.sin(ang) * cs_scale
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _attention(h, p, split, *, dims, quant, low):
    m = dims["mla"]
    nh = dims["num_heads"]
    dn, dr, dv, r = (m["qk_nope_dim"], m["qk_rope_dim"], m["v_dim"],
                     m["kv_lora_rank"])
    t = h.shape[0]
    eps = dims["norm_eps"]
    w, dec = _weights(p["attn"], ("wq", "w_dkv", "wo"), quant)
    x = _rms(h, p["ln1"].astype(F32), eps)
    q = _proj(x, w["wq"], dec["wq"], split, low).reshape(t, nh, dn + dr)
    kv = _proj(x, w["w_dkv"], dec["w_dkv"], split, low)
    c = _rms(kv[:, :r], p["attn"]["kv_norm"]["norm_w"].astype(F32), eps)
    w_uk = p["attn"]["w_uk"].astype(F32)
    w_uv = p["attn"]["w_uv"].astype(F32)
    k_nope = _mm(c, w_uk, low).reshape(t, nh, dn)
    v = _mm(c, w_uv, low).reshape(t, nh, dv)
    rs = dims.get("rope_scaling")
    scale = (dn + dr) ** -0.5
    cs = 1.0
    if rs is not None:
        cs = (_mscale(rs["factor"], rs["mscale"])
              / _mscale(rs["factor"], rs["mscale_all_dim"]))
        if rs["mscale_all_dim"]:
            scale *= _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    inv = yarn_inv_freq(dims)
    q_pe = _rope(q[..., dn:], inv, cs)
    k_pe = _rope(kv[:, r:], inv, cs)                           # (T, dr)
    qf = jnp.concatenate([q[..., :dn], q_pe], -1)
    kf = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe[:, None],
                                                   (t, nh, dr))], -1)
    if low:
        qf, kf, v = _fp8(qf), _fp8(kf), _fp8(v)
    s = jnp.einsum("thd,shd->hts", qf, kf, precision=HIGHEST) * scale
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    if low:
        pr = _fp8(pr)
    o = jnp.einsum("hts,shd->thd", pr, v, precision=HIGHEST)
    o = o.reshape(t, nh * dv)
    return h + _proj(o, w["wo"], dec["wo"], split, low)


def _swiglu(x, w, dec, split, low):
    def ffn(g, u, d):
        return _mm(jax.nn.silu(_mm(x, g, low)) * _mm(x, u, low), d, low)
    y = ffn(w["w_gate"], w["w_up"], w["w_down"])
    if dec["w_down"] is None:
        return y
    return _pick(y, ffn(dec["w_gate"], dec["w_up"], dec["w_down"]), split)


def _dense_layer(h, p, split, *, dims, quant, low):
    h = _attention(h, p, split, dims=dims, quant=quant, low=low)
    x = _rms(h, p["ln2"].astype(F32), dims["norm_eps"])
    w, dec = _weights(p["mlp"], ("w_gate", "w_up", "w_down"), quant)
    return h + _swiglu(x, w, dec, split, low)


def _moe_layer(h, blocks, i, split, *, dims, quant, low):
    mc = dims["moe"]
    p = jax.tree.map(lambda a: a[i], blocks)
    h = _attention(h, p, split, dims=dims, quant=quant, low=low)
    x = _rms(h, p["ln2"].astype(F32), dims["norm_eps"])
    probs = jax.nn.softmax(_mm(x, p["moe"]["router"].astype(F32), low), -1)
    top_p, top_e = jax.lax.top_k(probs, mc["top_k"])
    names = ("w_gate", "w_up", "w_down")
    w, dec = _weights(p["moe"], names, quant)
    out = _swiglu(x, *_weights(p["moe"]["shared"], names, quant), split,
                  low)
    for e in range(mc["num_experts"]):
        gate = jnp.sum(jnp.where(top_e == mc["first_held"] + e, top_p, 0.0),
                       -1)                                    # (T,)
        w_e = {k: v[e] for k, v in w.items()}
        dec_e = {k: (None if v is None else v[e]) for k, v in dec.items()}
        out = out + gate[:, None] * _swiglu(x, w_e, dec_e, split, low)
    return h + out


def _head(h, ln_f, lm_head, *, dims, low):
    x = _rms(h, ln_f.astype(F32), dims["norm_eps"])
    return _mm(x, lm_head.astype(F32), low)


class Reference:
    """The reference forward pass of one model configuration.

    ``dims``: the configuration file's ``model`` section plus its
    ``family`` (``"moe"``).  ``params``: the parameter tree.
    ``decode_nf4``: leaf names whose decode weights are NF4.
    ``seq_len``: every sequence is padded to this many positions (one
    compiled program per layer kind; causal, so padding changes nothing
    before it).
    """

    def __init__(self, dims: dict, params, *, seq_len: int,
                 decode_nf4=(), low_precision: bool = False):
        self.dims = dims
        self.params = params
        self.seq_len = seq_len
        kw = dict(dims=dims, quant=frozenset(decode_nf4), low=low_precision)
        self._dense = jax.jit(functools.partial(_dense_layer, **kw))
        self._moe = jax.jit(functools.partial(_moe_layer, **kw))
        self._head = jax.jit(functools.partial(_head, dims=dims,
                                               low=low_precision))
        self._embed = jax.jit(lambda e, ids: e[ids].astype(F32))

    def logits(self, prompt, served):
        """(len(served), vocab) float32 logits: row ``j`` is the
        reference's distribution for ``served[j]``, given the prompt and
        ``served[:j]``."""
        p = self.params
        ids = list(prompt) + list(served[:-1])
        t = len(ids)
        if t > self.seq_len:
            raise ValueError(f"sequence of {t} > seq_len {self.seq_len}")
        ids = np.asarray(ids + [0] * (self.seq_len - t), np.int32)
        split = jnp.int32(len(prompt))
        with jax.default_matmul_precision("highest"):
            h = self._embed(p["embed"], ids)
            for blk in p.get("dense_blocks", ()):
                h = self._dense(h, blk, split)
            n = jax.tree.leaves(p["blocks"])[0].shape[0]
            for i in range(n):
                h = self._moe(h, p["blocks"], i, split)
            rows = jax.lax.dynamic_slice_in_dim(h, len(prompt) - 1,
                                                len(served), 0)
            return self._head(rows, p["ln_f"], p["lm_head"])
