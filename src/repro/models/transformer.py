"""Decoder-only transformer LM (dense / MoE / MLA), scan-over-layers.

Design notes for scale:
  * layers are stacked (leading L axis) and executed with ``lax.scan`` —
    compile time and HLO size are depth-independent (95-layer deepseek-67b
    compiles as fast as 2 layers);
  * training wraps the block in ``jax.checkpoint`` (full remat policy) so
    the 4k x 256 train cells fit;
  * the LM loss is computed in sequence chunks so (S, vocab) logits are
    never materialized (minitron's 256k vocab would be 67 GB/device);
  * KV caches are stacked per layer and threaded through the scan as xs/ys.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.core.layers import quant_matmul
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models.attention import KVCache, init_gqa, init_mla
from repro.models.common import (CacheSpec, dense_init, embed_init,
                                 gather_last, remat_policy_of, rms_norm,
                                 token_positions)
from repro.models.mlp import init_mlp, mlp


def _block_init(key, cfg, *, use_moe: bool, d_ff: int | None = None):
    ks = jax.random.split(key, 3)
    p = {
        "ln1": jnp.ones((cfg.d_model,), jnp.float32),
        "ln2": jnp.ones((cfg.d_model,), jnp.float32),
        "attn": init_mla(ks[0], cfg) if cfg.mla else init_gqa(ks[0], cfg),
    }
    if use_moe:
        p["moe"] = moe_mod.init_moe(ks[1], cfg)
    else:
        p["mlp"] = init_mlp(ks[1], cfg, d_ff=d_ff)
    return p


def _block_apply(p, x, cfg, *, positions, cache, cache_index, use_moe: bool,
                 block_tables=None, n_valid=None, training: bool = False):
    """One block: returns (x, new_cache, aux loss, routed experts
    (B, S, top_k) int32 of an MoE block, else None)."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if cfg.mla:
        with jax.named_scope("mla.attn"):
            a, new_cache = attn_mod.mla_attention(
                p["attn"], h, cfg, positions=positions, cache=cache,
                cache_index=cache_index, block_table=block_tables,
                n_valid=n_valid)
    else:
        a, new_cache = attn_mod.gqa_attention(
            p["attn"], h, cfg, positions=positions, cache=cache,
            cache_index=cache_index, block_table=block_tables,
            n_valid=n_valid)
    x = x + a
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    routes = None
    if use_moe:
        f, aux, routes = moe_mod.moe_ffn(p["moe"], h, cfg, training=training)
    else:
        f = mlp(p["mlp"], h, cfg)
    return x + f, new_cache, aux, routes


class TransformerLM:
    """Generic decoder-only LM covering dense, MoE and MLA families."""

    def __init__(self, cfg):
        self.cfg = cfg

    # ---------------- params ----------------
    def init(self, key) -> dict:
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        k_e, k_h, k_0, k_L = jax.random.split(key, 4)
        moe = cfg.moe
        n_dense = moe.first_dense if moe else 0
        n_scan = cfg.num_layers - n_dense
        params: dict[str, Any] = {
            "embed": embed_init(k_e, cfg.vocab_size, cfg.d_model, dt),
            "ln_f": jnp.ones((cfg.d_model,), jnp.float32),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(k_h, cfg.d_model, cfg.vocab_size, dt)
        if n_dense:
            dense_ff = moe.dense_ff or cfg.d_ff
            params["dense_blocks"] = [
                _block_init(k, cfg, use_moe=False, d_ff=dense_ff)
                for k in jax.random.split(k_0, n_dense)]
        params["blocks"] = jax.vmap(
            lambda k: _block_init(k, cfg, use_moe=moe is not None)
        )(jax.random.split(k_L, n_scan))
        return params

    # ---------------- forward ----------------
    def _scan_blocks(self, params, x, *, positions, caches, cache_index,
                     training: bool, block_tables=None, n_valid=None,
                     routes: bool = False):
        """``routes``: the scan also yields each MoE block's routed
        experts, and the new caches come back as (caches, routes)."""
        cfg = self.cfg
        use_moe = cfg.moe is not None
        from repro.parallel.act_sharding import shard_hidden

        def body(carry, xs):
            h, aux = carry
            p_i, cache_i = xs
            h = shard_hidden(h)
            h2, new_cache, aux_i, top_e = _block_apply(
                p_i, h, cfg, positions=positions, cache=cache_i,
                cache_index=cache_index, use_moe=use_moe,
                block_tables=block_tables, n_valid=n_valid,
                training=training)
            ys = (new_cache, top_e) if routes else new_cache
            return (shard_hidden(h2), aux + aux_i), ys

        if training and cfg.remat:
            body = jax.checkpoint(
                body, policy=remat_policy_of(cfg))

        if not cfg.scan_layers:
            # accounting/probe mode: python loop (exact cost_analysis totals)
            aux = jnp.zeros((), jnp.float32)
            n = jax.tree.leaves(params["blocks"])[0].shape[0]
            new_caches = []
            carry = (x, aux)
            for i in range(n):
                p_i = jax.tree.map(lambda a: a[i], params["blocks"])
                c_i = (jax.tree.map(lambda a: a[i], caches)
                       if caches is not None else None)
                carry, nc = body(carry, (p_i, c_i))
                new_caches.append(nc)
            x, aux = carry
            if caches is not None:
                new_caches = jax.tree.map(lambda *xs: jnp.stack(xs, 0),
                                          *new_caches)
            else:
                new_caches = None
            return x, aux, new_caches

        (x, aux), new_caches = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)),
            (params["blocks"], caches))
        return x, aux, new_caches

    def forward(self, params, tokens=None, *, embeds=None, caches=None,
                cache_index=0, training: bool = False, block_tables=None,
                n_valid=None, routes: bool = False):
        """Returns (hidden (B,S,D), aux, new_caches); with ``routes``
        (MoE) also the routed experts of every MoE block,
        (L_moe, B, S, top_k) int32."""
        cfg = self.cfg
        if embeds is None:
            embeds = params["embed"][tokens]
        x = embeds
        b, s, _ = x.shape
        positions = token_positions(s, cache_index)
        moe = cfg.moe
        n_dense = moe.first_dense if moe else 0
        dense_caches, scan_caches = None, None
        if caches is not None:
            dense_caches, scan_caches = caches
        new_dense_caches = []
        for i in range(n_dense):
            c = dense_caches[i] if dense_caches is not None else None
            x, nc, _, _ = _block_apply(
                params["dense_blocks"][i], x, cfg, positions=positions,
                cache=c, cache_index=cache_index, use_moe=False,
                block_tables=block_tables, n_valid=n_valid)
            new_dense_caches.append(nc)
        x, aux, new_scan = self._scan_blocks(
            params, x, positions=positions,
            caches=scan_caches if scan_caches is not None else _none_caches(
                cfg.num_layers - n_dense),
            cache_index=cache_index, training=training,
            block_tables=block_tables, n_valid=n_valid, routes=routes)
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        if routes:
            new_scan, top_e = new_scan
        new_caches = (new_dense_caches, new_scan) if caches is not None else None
        if routes:
            return x, aux, new_caches, top_e
        return x, aux, new_caches

    def logits(self, params, hidden):
        cfg = self.cfg
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        return quant_matmul(hidden, head, None)

    # ---------------- training ----------------
    def loss(self, params, batch):
        """batch: tokens (B, S), labels (B, S)[, embeds for VLM]."""
        cfg = self.cfg
        hidden, aux, _ = self.forward(
            params, batch.get("tokens"), embeds=batch.get("embeds"),
            training=True)
        head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
        labels = batch["labels"]
        mask = batch.get("loss_mask")
        xent = chunked_xent(hidden, head, labels, mask,
                            unroll=not cfg.scan_layers)
        return xent + aux, {"xent": xent, "aux": aux}

    # ---------------- serving ----------------
    def init_cache(self, batch: int, s_max: int, *,
                   spec: CacheSpec | None = None) -> tuple:
        """Dense slab caches (B, s_max, ...) by default.  With a paged
        ``spec``, every KV leaf becomes a paged pool
        (num_blocks, block_size, ...) shared by all slots and indexed via a
        per-row block table (``batch``/``s_max`` then only size the layout,
        not the leaves)."""
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        moe = cfg.moe
        n_dense = moe.first_dense if moe else 0
        n_scan = cfg.num_layers - n_dense
        if spec is not None and spec.paged:
            lead = (spec.num_blocks, spec.block_size)
        else:
            lead = (batch, s_max)

        def one():
            if cfg.mla:
                m = cfg.mla
                tails = ((m.kv_lora_rank,), (m.qk_rope_dim,))
            else:
                hkv, dh = cfg.num_kv_heads, cfg.resolved_head_dim
                tails = ((hkv, dh), (hkv, dh))
            return KVCache(jnp.zeros(lead + tails[0], dt),
                           jnp.zeros(lead + tails[1], dt))

        dense_caches = [one() for _ in range(n_dense)]
        one_c = one()
        scan_caches = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n_scan,) + a.shape).copy(),
            one_c)
        return (dense_caches, scan_caches)

    def prefill(self, params, tokens, caches, *, embeds=None, last_pos=None,
                cache_index=0):
        """``last_pos``: optional (B,) per-row index of the last REAL token
        (right-padded batched prefill); default = the final column.
        ``cache_index``: scalar write offset — chunked prefill feeds the
        prompt in pieces, each continuing at the previous chunk's end."""
        hidden, _, new_caches = self.forward(
            params, tokens, embeds=embeds, caches=caches,
            cache_index=cache_index)
        last = (hidden[:, -1:] if last_pos is None
                else gather_last(hidden, last_pos))
        logits = self.logits(params, last)
        return logits, new_caches

    def decode_step(self, params, token, state, index, *, tables=None,
                    routes: bool = False):
        """token: (B, 1) int32; index: scalar int32 position shared by all
        rows, or a (B,) int32 array of per-row positions (mixed-depth
        continuous batching).  ``tables``: (B, nblk) int32 block tables
        when ``state`` holds paged pools (see ``init_cache``).

        ``params`` may be the engine's frozen 4-bit decode tree
        (``EngineConfig(quant=...)``): attention/MLP projection leaves are
        then ``QuantizedWeight`` containers that ``quant_matmul`` routes
        through the D&C LUT gemm — scan-stacked leaves slice per layer
        like any float leaf (registered pytree with a leading L axis).

        ``routes`` (MoE): also return the routed experts of every MoE
        block, (L_moe, B, 1, top_k) int32."""
        out = self.forward(params, token, caches=state, cache_index=index,
                           block_tables=tables, routes=routes)
        logits = self.logits(params, out[0])
        return (logits, out[2], out[3]) if routes else (logits, out[2])

    def decode_window(self, params, tokens, state, index, *, tables=None,
                      n_valid=None, last_pos=None):
        """Speculative verify: score a (B, W) window of already-chosen
        tokens in ONE batched forward.  ``index``: (B,) per-row positions
        of window column 0; ``n_valid``: (B,) real tokens per row (the
        rest write nowhere and are masked out of attention — inactive rows
        pass 0 and touch nothing).  ``last_pos`` is accepted for signature
        uniformity with the recurrent families and ignored: KV beyond a
        row's rewound pointer is dead weight the next writes overwrite, so
        the verify-pass cache IS the committed cache at any accept length.

        Returns (logits (B, W, V), new_caches) — logits[:, i] scores the
        token AFTER window column i."""
        del last_pos
        hidden, _, new_caches = self.forward(
            params, tokens, caches=state, cache_index=index,
            block_tables=tables, n_valid=n_valid)
        return self.logits(params, hidden), new_caches


def _none_caches(n: int):
    return None


def chunked_xent(hidden, head, labels, mask=None, chunk: int = 256,
                 unroll: bool = False):
    """Sequence-chunked cross entropy: never materializes (S, V) logits."""
    b, s, d = hidden.shape
    if s <= chunk:
        logits = (hidden @ head).astype(jnp.float32)
        return _xent(logits, labels, mask)
    nc = s // chunk
    assert s % chunk == 0, (s, chunk)

    def piece(h, lab, m):
        logits = (h @ head).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        return ((logz - gold) * m).sum(), m.sum()

    if mask is None:
        mask = jnp.ones((b, s), jnp.float32)
    if unroll:
        tot = cnt = 0.0
        for i in range(nc):
            sl = slice(i * chunk, (i + 1) * chunk)
            t, c = piece(hidden[:, sl], labels[:, sl], mask[:, sl])
            tot, cnt = tot + t, cnt + c
        return tot / jnp.maximum(cnt, 1.0)

    hs = hidden.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(b, nc, chunk).transpose(1, 0, 2)
    ms = mask.reshape(b, nc, chunk).transpose(1, 0, 2)

    def step(acc, xs):
        t, c = piece(*xs)
        return (acc[0] + t, acc[1] + c), None

    (tot, cnt), _ = jax.lax.scan(step, (jnp.zeros(()), jnp.zeros(())),
                                 (hs, ls, ms))
    return tot / jnp.maximum(cnt, 1.0)


def _xent(logits, labels, mask=None):
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()
