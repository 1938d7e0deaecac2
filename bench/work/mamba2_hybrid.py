"""The decode step of a Mamba2 model (family ``ssm``) and of a Zamba2
hybrid (family ``hybrid``), counted from the configuration's shapes.

A decode step of ``rows`` active rows reads every weight once, reads and
writes each row's recurrent state, and, for the shared attention of a
hybrid, reads each row's keys and values up to its position and writes
one more.  Beyond the matrix products, FLOPs count the SSM state update
and read-out, the depthwise convolution and attention scores and values.
"""
from __future__ import annotations

import math

from work import BF16, F32, Work, head, matrix_bytes


def mamba2_dims(model: dict) -> dict:
    s = model["ssm"]
    d = model["d_model"]
    di = s["expand"] * d
    nh = di // s["head_dim"]
    gn = s["num_groups"] * s["state_dim"]
    return dict(d=d, di=di, nh=nh, hp=s["head_dim"], n=s["state_dim"],
                conv_ch=di + 2 * gn, k=s["conv_dim"],
                in_dim=2 * di + 2 * gn + nh)


def mamba2_layer(model: dict, rows: int, frozen=frozenset()) -> Work:
    """One Mamba2 layer of a decode step over ``rows`` rows."""
    m = mamba2_dims(model)
    d, di, nh, hp, n = m["d"], m["di"], m["nh"], m["hp"], m["n"]
    w = (matrix_bytes(d, m["in_dim"], "w_in" in frozen)
         + matrix_bytes(di, d, "w_out" in frozen)
         + m["k"] * m["conv_ch"] * BF16 + m["conv_ch"] * BF16   # conv w, b
         + 3 * nh * F32                                         # A, D, dt
         + di * BF16 + d * F32)                                 # norm, ln
    state = (2 * nh * hp * n * F32                              # r + w
             + 2 * (m["k"] - 1) * m["conv_ch"] * BF16)
    flops = (2 * d * m["in_dim"] + 2 * di * d + 5 * nh * hp * n
             + 2 * m["k"] * m["conv_ch"])
    return Work(rows * flops, w + rows * state)


def shared_block(model: dict, rows: int, keys: int,
                 frozen=frozenset()) -> Work:
    """One application of the hybrid's shared attention + MLP block:
    weights, ``rows`` rows of projections, and attention over ``keys``
    keys summed over the rows (each row's position + 1)."""
    h = model["hybrid"]
    d, ff = model["d_model"], h["shared_d_ff"]
    nq, nkv = h["shared_num_heads"], h["shared_num_kv_heads"]
    dh = d // nq
    mats = {"wq": (d, nq * dh), "wk": (d, nkv * dh), "wv": (d, nkv * dh),
            "wo": (nq * dh, d), "w_gate": (d, ff), "w_up": (d, ff),
            "w_down": (ff, d)}
    w = sum(matrix_bytes(k, n, name in frozen)
            for name, (k, n) in mats.items()) + 2 * d * F32
    proj = sum(2 * k * n for k, n in mats.values())
    kv_rw = (keys + rows) * nkv * dh * 2 * BF16   # read all, write one
    return Work(rows * proj + 4 * nq * dh * keys, w + kv_rw)


def decode_step(model: dict, family: str, rows: float, keys: float,
                frozen=frozenset()) -> Work:
    """A whole decode step: ``rows`` active rows, ``keys`` the sum over
    them of their positions + 1 (keys each attends to)."""
    work = mamba2_layer(model, rows, frozen).scale(model["num_layers"])
    if family == "hybrid":
        apps = math.ceil(model["num_layers"] / model["hybrid"]["period"])
        # the shared block's weights are read once a step (the least a
        # step needs); its activations and keys at every application
        one = shared_block(model, rows, keys, frozen)
        w_once = shared_block(model, 0, 0, frozen).bytes
        work = work + Work(apps * one.flops,
                           w_once + apps * (one.bytes - w_once))
    return work + head(model, rows)
