"""The dense llama-family decoder (llama, mistral, granite): its
configuration file mapped onto the program's ``ModelConfig``, and the
shape counts of ``benchlib.counts`` that its decode metrics read.

Keys read: ``num_hidden_layers``, ``hidden_size``, ``intermediate_size``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim`` (else
``hidden_size / num_attention_heads``), ``vocab_size``, one uniform
``sliding_window`` (null: full attention), ``rope_theta``,
``rms_norm_eps``, ``tie_word_embeddings`` and ``torch_dtype``.  Every
leaf of the program's tree is covered by ``serve.make_weights``' default
rule, so there is no ``init_scale``.
"""
from __future__ import annotations

from typing import Any, Dict

from benchlib.counts import decode_needs  # noqa: F401
from benchlib.counts import dense_dims as dims


def program_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig

    d = dims(conf)
    return ModelConfig(
        name=conf["name"], family="dense", n_layers=d.layers,
        d_model=d.d, n_heads=d.heads, n_kv_heads=d.kv_heads,
        d_ff=d.ff, vocab=d.vocab, head_dim=d.head_dim,
        swa_window=conf.get("sliding_window"),
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        dtype=conf["torch_dtype"])
