"""Operations and bytes the algorithms need, computed from shapes alone.

These counts are the benchmark's yardstick: they do not depend on how
the program implements a step, so a later change that fuses, reorders
or tiles the work is measured against the same numbers.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Mapping


@dataclasses.dataclass(frozen=True)
class DenseDims:
    """The shape of a dense llama-family decoder, from its config file."""

    layers: int
    d: int
    ff: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    tied: bool

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def layer_matmul_params(self) -> int:
        """q, k, v, o projections and the gate, up and down matrices."""
        d = self.d
        return (d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
                + 3 * d * self.ff)


def dense_dims(conf: Mapping) -> DenseDims:
    heads = conf["num_attention_heads"]
    return DenseDims(
        layers=conf["num_hidden_layers"], d=conf["hidden_size"],
        ff=conf["intermediate_size"], heads=heads,
        kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim") or conf["hidden_size"] // heads,
        vocab=conf["vocab_size"], tied=bool(conf["tie_word_embeddings"]))


def weight_elements(dims: DenseDims) -> int:
    """Every parameter: embedding (and untied output head), the layers'
    matrices and norms, and the final norm."""
    head = 0 if dims.tied else dims.vocab * dims.d
    per_layer = dims.layer_matmul_params + 2 * dims.d
    return dims.vocab * dims.d + head + dims.layers * per_layer + dims.d


def weight_bytes(dims: DenseDims, itemsize: int = 2) -> int:
    return weight_elements(dims) * itemsize


def decode_token_flops(dims: DenseDims, context: int) -> int:
    """Model FLOPs of one decoded token that attends over ``context``
    cached positions (its own included): every matrix product, the output
    head, and the scores and weighted sum of attention."""
    matmul = dims.layers * dims.layer_matmul_params + dims.vocab * dims.d
    attn = 4 * dims.layers * dims.q_dim * context
    return 2 * matmul + attn


def decode_needs(dims: DenseDims, steps: int, contexts: Iterable[int],
                 itemsize: int = 2):
    """(FLOPs, bytes) the least ``steps`` decode steps must do, given for
    each live slot in each step the positions its new token attends over
    (``contexts``).  Bytes: per step, every weight the step needs once
    (all layer matrices and norms, the final norm, the output head); per
    live token its embedding row where the head is untied, the K and V
    of every position it attends over read, and its own K/V row
    written."""
    contexts = list(contexts)
    flops = sum(decode_token_flops(dims, c) for c in contexts)
    per_pos = dims.layers * 2 * dims.kv_dim * itemsize
    gathered = 0 if dims.tied else dims.vocab * dims.d
    step_weights = (weight_elements(dims) - gathered) * itemsize
    per_token = per_pos + (0 if dims.tied else dims.d * itemsize)
    kv_read = sum(contexts) * per_pos
    return flops, steps * step_weights + kv_read + len(contexts) * per_token


def layer_graph_flops(l: int, d: int, f: int, dv: int = 0) -> int:
    """Algebra FLOPs of the single-head layer graph: the q, k and v
    projections, scores, attend, output projection, up and down."""
    dv = dv or d
    macs = (2 * l * d * d + dv * l * d + l * l * d + l * dv * l
            + l * d * dv + l * f * d + l * d * f)
    return 2 * macs


def layer_graph_bytes(l: int, d: int, f: int, dv: int = 0,
                      itemsize: int = 2) -> int:
    """The least bytes one call moves: its inputs and weights read once
    and its output written once."""
    dv = dv or d
    elems = (l * d + 2 * d * d + dv * d + d * dv + f * d + f + d * f
             + l * d)
    return elems * itemsize
