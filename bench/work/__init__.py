"""The operations and bytes a decode step needs, from a configuration's
shapes alone (not from the program, its HLO or its storage).

What every family shares is here: :class:`Work`, a frozen or stored
matrix's bytes and the output head.  Each configuration names the module
beside this file that counts its whole decode step (its ``"work"`` key,
``bench/work/<work>.py``); that module exposes
``decode_step(model, family, rows, keys, frozen) -> Work``.

A projection frozen to NF4 counts 4 bits a weight plus its float32
column scale and its code tables (16 + 4 + 4 float32); every other
weight counts at its stored width (bf16 matrices, float32 gains and SSM
scalars).  FLOPs count the matrix products; elementwise work is left
out.
"""
from __future__ import annotations

from dataclasses import dataclass

BF16, F32 = 2, 4
NF4_TABLE_BYTES = (16 + 4 + 4) * F32


@dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, o):
        return Work(self.flops + o.flops, self.bytes + o.bytes)

    def scale(self, k: float) -> "Work":
        return Work(self.flops * k, self.bytes * k)

    def least_s(self, peaks) -> tuple[float, str]:
        """The least time on a chip with ``peaks``, and what bounds it."""
        tc = self.flops / peaks.flops_bf16
        tm = self.bytes / peaks.hbm_bytes_s
        return (tc, "compute") if tc >= tm else (tm, "memory")


def matrix_bytes(k: int, n: int, frozen: bool, bits: int = 4) -> float:
    if frozen:
        return k * n * bits / 8 + n * F32 + NF4_TABLE_BYTES
    return k * n * BF16


def head(model: dict, rows: int) -> Work:
    """Embedding gather, final norm and the (unfrozen) output head."""
    d, v = model["d_model"], model["vocab_size"]
    return Work(rows * 2 * d * v, d * v * BF16 + rows * d * BF16 + d * F32)
