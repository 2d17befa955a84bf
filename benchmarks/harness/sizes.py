"""A configuration's sizes, read from its file under ``benchmarks/configs``.

The file keeps the keys of the model's published ``config.json`` (so that it
can be compared with its source key by key); this module gives them the
short names the rest of the benchmark uses and, through the file's
``program`` group, the keyword arguments of the program's own config class.
"""

import dataclasses
import importlib
import json
from pathlib import Path


@dataclasses.dataclass(frozen=True)
class Sizes:
    hidden: int
    ffn: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    rope_theta: float
    rms_eps: float
    tied: bool
    dtype: str

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim


def load_config(path) -> dict:
    """The configuration file as a dict; refuses one without a source."""
    cfg = json.loads(Path(path).read_text())
    for key in ("source", "reference", "program"):
        if key not in cfg:
            raise ValueError(f"{path}: missing {key!r}")
    return cfg


def sizes_of(cfg: dict) -> Sizes:
    heads = cfg["num_attention_heads"]
    return Sizes(
        hidden=cfg["hidden_size"], ffn=cfg["intermediate_size"],
        layers=cfg["num_hidden_layers"], heads=heads,
        kv_heads=cfg.get("num_key_value_heads", heads),
        head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
        vocab=cfg["vocab_size"], rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        tied=bool(cfg["tie_word_embeddings"]),
        dtype=cfg["torch_dtype"])


def program_config(cfg: dict):
    """Build the program's config object as the file's ``program`` group
    says: ``class`` is ``module:Name``, ``fields`` maps each keyword of that
    class to the key of this file that holds its value."""
    module, _, name = cfg["program"]["class"].partition(":")
    cls = getattr(importlib.import_module(module), name)
    kwargs = {field: cfg[key] for field, key in cfg["program"]["fields"].items()}
    if "dtype" in kwargs:
        import jax.numpy as jnp

        kwargs["dtype"] = jnp.dtype(kwargs["dtype"])
    return cls(**kwargs)
