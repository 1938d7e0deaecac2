"""Plain float32 reference forward passes of a Mamba2 model (family
``ssm``) and of a Zamba2 hybrid (family ``hybrid``).

Straightforward ``jax.numpy`` at ``jax.default_matmul_precision("highest")``:
no cache, no batching, no kernels, one sequence at a time, one layer per
call.  It imports nothing of the program and reads only the parameter
tree the benchmark made (``bench/weights.py``), by leaf name.

The equations, per token sequence ``x`` of length T:

* Mamba2 layer (Dao & Gu 2024, arXiv:2405.21060): ``h += out_proj(
  rmsnorm(y * silu(z)) * norm_w)`` where ``[z, xBC, dt] = in_proj(
  rmsnorm(h) * ln)``, ``xBC`` goes through a causal depthwise conv of
  width ``conv_dim`` and SiLU, ``dt = softplus(dt + dt_bias)``, and the
  SSD output is the masked quadratic form
  ``y_t = sum_{s<=t} exp(sum_{s<r<=t} dt_r A) (C_t . B_s) dt_s x_s + D x_t``
  with ``A = -exp(A_log)``.
* Shared block (hybrid): ``h += attn(rmsnorm(h) * ln1)`` with RoPE on
  interleaved pairs and causal softmax, then
  ``h += w_down(silu(w_gate x) * w_up x)`` on ``rmsnorm(h) * ln2``; it is
  applied before each group of ``period`` Mamba2 layers.
* Logits: ``rmsnorm(h) * ln_f @ lm_head``.

Two weight sets can be given: positions before ``split`` use the
prefill weights, the rest the decode weights.  A served model whose
decode projections are frozen to NF4 is checked this way: the prompt
runs through the full-precision weights, every decoded token through
weights this module quantizes itself (``nf4.py``).

``low_precision=True`` is the control: every matrix product's operands
are rounded to float8 e4m3 (per-tensor absmax scaling) before an exact
product.
"""
from __future__ import annotations

import functools

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


def _proj(x, w, w_dec, split, low):
    """``x @ w`` at positions before ``split``, ``x @ w_dec`` after."""
    y = _mm(x, w, low)
    if w_dec is None:
        return y
    y_dec = _mm(x, w_dec, low)
    pre = (jnp.arange(x.shape[0]) < split)[:, None]
    return jnp.where(pre, y, y_dec)


def _weights(tree, i, names, quant):
    """Layer ``i``'s leaves ``names`` of a stacked tree, in f32, and the
    decode copies of those in ``quant`` (NF4), or None."""
    w = {k: tree[k][i].astype(F32) if i is not None else tree[k].astype(F32)
         for k in names}
    dec = {k: (nf4_dequant(w[k]) if k in quant else None) for k in names}
    return w, dec


def _mamba_layer(h, ln, m, i, split, *, dims, quant, low):
    eps = dims["norm_eps"]
    s = dims["ssm"]
    d = dims["d_model"]
    di = s["expand"] * d
    hp = s["head_dim"]
    nh = di // hp
    g, n = s["num_groups"], s["state_dim"]
    conv_ch = di + 2 * g * n
    t = h.shape[0]
    w, dec = _weights(m, i, ("w_in", "w_out"), quant)
    x = _rms(h, ln[i].astype(F32), eps)
    zxbcdt = _proj(x, w["w_in"], dec["w_in"], split, low)
    z = zxbcdt[:, :di]
    xbc = zxbcdt[:, di:di + conv_ch]
    dt = jax.nn.softplus(zxbcdt[:, di + conv_ch:]
                         + m["dt_bias"][i].astype(F32))          # (T, H)
    cw = m["conv_w"][i].astype(F32)                               # (K, C)
    k = cw.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, conv_ch), F32), xbc], 0)
    conv = sum(cw[j] * xp[j:j + t] for j in range(k))
    conv = jax.nn.silu(conv + m["conv_b"][i].astype(F32))
    xs = conv[:, :di].reshape(t, nh, hp)
    bm = conv[:, di:di + g * n].reshape(t, g, n)
    cm = conv[:, di + g * n:].reshape(t, g, n)
    a = -jnp.exp(m["A_log"][i].astype(F32))                       # (H,)
    cum = jnp.cumsum(dt * a, axis=0)                              # (T, H)
    causal = jnp.tril(jnp.ones((t, t), bool))
    seg = jnp.where(causal[:, :, None], cum[:, None, :] - cum[None, :, :],
                    -jnp.inf)
    decay = jnp.exp(seg)                                          # (T, T, H)
    cb = jnp.einsum("tgn,sgn->tsg", cm, bm, precision=HIGHEST)
    cb = jnp.repeat(cb, nh // g, axis=2)                          # (T, T, H)
    y = jnp.einsum("tsh,sh,shp->thp", decay * cb, dt, xs,
                   precision=HIGHEST)
    y = y + m["D"][i].astype(F32)[None, :, None] * xs
    y = y.reshape(t, di) * jax.nn.silu(z)
    y = _rms(y, m["norm_w"][i].astype(F32), eps)
    return h + _proj(y, w["w_out"], dec["w_out"], split, low)


def _rope(x, theta):
    """Rotate interleaved pairs ``(x[2i], x[2i+1])`` of (T, H, Dh)."""
    t, _, dh = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(t, dtype=F32)[:, None] * freqs[None, :]     # (T, Dh/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _shared_block(h, p, split, *, dims, quant, low):
    eps = dims["norm_eps"]
    hc = dims["hybrid"]
    d = dims["d_model"]
    nh, nkv = hc["shared_num_heads"], hc["shared_num_kv_heads"]
    dh = d // nh
    t = h.shape[0]
    w, dec = _weights(p["attn"], None, ("wq", "wk", "wv", "wo"), quant)
    x = _rms(h, p["ln1"].astype(F32), eps)
    q = _proj(x, w["wq"], dec["wq"], split, low).reshape(t, nh, dh)
    k = _proj(x, w["wk"], dec["wk"], split, low).reshape(t, nkv, dh)
    v = _proj(x, w["wv"], dec["wv"], split, low).reshape(t, nkv, dh)
    q, k = _rope(q, dims["rope_theta"]), _rope(k, dims["rope_theta"])
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    if low:
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    s = jnp.einsum("thd,shd->hts", q, k, precision=HIGHEST) / np.sqrt(dh)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    if low:
        pr = _fp8(pr)
    o = jnp.einsum("hts,shd->thd", pr, v, precision=HIGHEST)
    h = h + _proj(o.reshape(t, nh * dh), w["wo"], dec["wo"], split, low)
    w, dec = _weights(p["mlp"], None, ("w_gate", "w_up", "w_down"), quant)
    x = _rms(h, p["ln2"].astype(F32), eps)
    f = (jax.nn.silu(_proj(x, w["w_gate"], dec["w_gate"], split, low))
         * _proj(x, w["w_up"], dec["w_up"], split, low))
    return h + _proj(f, w["w_down"], dec["w_down"], split, low)


def _head(h, ln_f, lm_head, *, dims, low):
    x = _rms(h, ln_f.astype(F32), dims["norm_eps"])
    return _mm(x, lm_head.astype(F32), low)


class Reference:
    """The reference forward pass of one model configuration.

    ``dims``: the configuration file's ``model`` section plus its
    ``family`` (``"ssm"`` or ``"hybrid"``).  ``params``: the parameter
    tree.  ``decode_nf4``: leaf names whose decode weights are NF4.
    ``seq_len``: every sequence is padded to this many positions (one
    compiled program per layer kind; causal, so padding changes nothing
    before it).
    """

    def __init__(self, dims: dict, params, *, seq_len: int,
                 decode_nf4=(), low_precision: bool = False):
        self.dims = dims
        self.params = params
        self.seq_len = seq_len
        quant = frozenset(decode_nf4)
        kw = dict(dims=dims, quant=quant, low=low_precision)
        self._mamba = jax.jit(functools.partial(_mamba_layer, **kw))
        self._shared = jax.jit(functools.partial(_shared_block, **kw))
        self._head = jax.jit(functools.partial(_head, dims=dims,
                                               low=low_precision))
        self._embed = jax.jit(lambda e, ids: e[ids].astype(F32))

    def _layers(self):
        """(kind, i) in forward order."""
        n = self.dims["num_layers"]
        if self.dims["family"] == "ssm":
            return [("mamba", i) for i in range(n)]
        period = self.dims["hybrid"]["period"]
        order = []
        for i in range(n):
            if i % period == 0:
                order.append(("shared", None))
            order.append(("mamba", i))
        return order

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
        stack = p["blocks"] if self.dims["family"] == "ssm" else p["mamba"]
        with jax.default_matmul_precision("highest"):
            h = self._embed(p["embed"], ids)
            for kind, i in self._layers():
                if kind == "shared":
                    h = self._shared(h, p["shared"], split)
                else:
                    h = self._mamba(h, stack["ln"], stack["m"], i, split)
            rows = jax.lax.dynamic_slice_in_dim(h, len(prompt) - 1,
                                                len(served), 0)
            return self._head(rows, p["ln_f"], p["lm_head"])
