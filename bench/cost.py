"""Operations and bytes of the work a window served, from shapes alone.

They depend only on what was served (tokens, their context lengths, the
TT plan's modes and ranks), never on how the program implements it, so
they stay fixed while later changes swap kernels underneath.  Operations
count a multiply and an add as two; a TT layer is counted at its
factorised cost (the paper's Eq. 11/13 without bias).

The TT arithmetic here is shared by every family.  ``Dims`` counts a
dense decoder (MHA or GQA, one gated FFN per layer); it is what the dense
reference module's ``dims`` gives.  A family with other layer equations
gives its own object with the same methods from its own reference module
(``bench/reference.py`` names them).
"""
from __future__ import annotations

import dataclasses
import math

KV_BYTES = 2                    # bf16 arenas


def tt_flops_per_row(ns, ms, ranks) -> int:
    """Eq. 13 summed over the cores: 2 · r_t · r_{t-1} · m_t…m_d ·
    n_1…n_t for t = 1 … d (one token)."""
    d = len(ns)
    return sum(2 * ranks[t] * ranks[t - 1] * math.prod(ms[t - 1:])
               * math.prod(ns[:t]) for t in range(1, d + 1))


def tt_core_params(ns, ms, ranks) -> int:
    return sum(ranks[t] * ns[t] * ms[t] * ranks[t + 1]
               for t in range(len(ns)))


def tt_call_bytes(ns, ms, ranks, rows: int, act_bytes: int = 2,
                  weight_bytes: int = 2) -> int:
    """HBM bytes one TT call must move at least: its input rows and output
    rows once, and its cores once."""
    return (rows * (math.prod(ns) + math.prod(ms)) * act_bytes
            + tt_core_params(ns, ms, ranks) * weight_bytes)


@dataclasses.dataclass(frozen=True)
class Dims:
    """A dense decoder's counts: every layer the same attention and FFN."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    ffn: tuple                  # ((ns, ms, ranks), ...) per FFN matrix

    @classmethod
    def from_config(cls, cfg: dict, ffn_chains) -> "Dims":
        return cls(int(cfg["num_hidden_layers"]), int(cfg["hidden_size"]),
                   int(cfg["num_attention_heads"]),
                   int(cfg["num_key_value_heads"]), int(cfg["head_dim"]),
                   int(cfg["vocab_size"]),
                   tuple(tuple(tuple(int(v) for v in part) for part in c)
                         for c in ffn_chains))

    def token_flops(self, ctx: int) -> int:
        """One token through every layer, attending to ``ctx`` positions
        (itself included); the LM head is counted apart."""
        d, q = self.d_model, self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        proj = 2 * d * (2 * q + 2 * kv)
        attn = 4 * ctx * q
        ffn = sum(tt_flops_per_row(*c) for c in self.ffn)
        return self.layers * (proj + attn + ffn)

    def prompt_flops(self, prompt_len: int) -> int:
        """A whole prompt, position p attending to p + 1 positions."""
        P = prompt_len
        q = self.heads * self.head_dim
        return (P * self.token_flops(0)
                + self.layers * 4 * q * (P * (P + 1) // 2))

    def head_flops(self) -> int:
        return 2 * self.d_model * self.vocab

    def tt_work(self, rows: int, calls_per_matrix: int) -> tuple[int, int]:
        """(flops, bytes) of the FFN's TT layers over ``rows`` token rows
        spread over ``calls_per_matrix`` calls of each matrix per layer:
        the rows move once, the cores once per call."""
        flops = byts = 0
        for c in self.ffn:
            flops += self.layers * rows * tt_flops_per_row(*c)
            act = tt_call_bytes(*c, rows=rows) - tt_call_bytes(*c, rows=0)
            byts += self.layers * (act + calls_per_matrix
                                   * tt_call_bytes(*c, rows=0))
        return flops, byts

    def kv_bytes_per_token(self) -> int:
        """The cache one position holds over every layer: a K and a V row
        of ``kv_heads × head_dim`` values."""
        return self.layers * 2 * self.kv_heads * self.head_dim * KV_BYTES


# the counts as functions of the dims, the form bench/tests call
token_flops = Dims.token_flops
prompt_flops = Dims.prompt_flops
head_flops = Dims.head_flops
tt_work = Dims.tt_work
