"""Read a configuration file (``bench/configs/<name>.json``) into the sizes
the harness and the reference use.

Configuration files keep the key names of the model's own published
``config.json``; those differ between model families (``num_hidden_layers``
against ``num_layers``, ``rope_theta`` against ``rope_ratio``), so each size
is looked up under every name it is published as.  A file whose keys none of
these cover brings a ``bench/configs/<name>.py`` with ``dims(raw)`` beside it.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

# size -> the published key names it may come under, first found wins
ALIASES = {
    "layers": ("num_hidden_layers", "num_layers"),
    "d_model": ("hidden_size",),
    "heads": ("num_attention_heads",),
    "kv_heads": ("num_key_value_heads", "multi_query_group_num"),
    "head_dim": ("head_dim", "kv_channels"),
    "d_ff": ("intermediate_size", "ffn_hidden_size"),
    "vocab": ("vocab_size", "padded_vocab_size"),
    "eps": ("rms_norm_eps", "layernorm_epsilon"),
}


@dataclasses.dataclass(frozen=True)
class Dims:
    family: str
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    eps: float
    tie: bool
    qk_norm: bool


def _pick(raw: dict, size: str):
    for key in ALIASES[size]:
        if key in raw:
            return raw[key]
    raise KeyError(f"configuration has none of {ALIASES[size]} for {size}")


def dims(raw: dict) -> Dims:
    """The served sizes of a configuration file's contents."""
    if "rope_theta" in raw:
        theta = float(raw["rope_theta"])
    else:                       # GLM's config.json: base 10000 times a ratio
        theta = 10000.0 * float(raw.get("rope_ratio", 1))
    heads = int(_pick(raw, "heads"))
    d_model = int(_pick(raw, "d_model"))
    try:
        head_dim = int(_pick(raw, "head_dim"))
    except KeyError:
        head_dim = d_model // heads
    return Dims(family=raw["family"], layers=int(_pick(raw, "layers")),
                d_model=d_model, heads=heads,
                kv_heads=int(_pick(raw, "kv_heads")), head_dim=head_dim,
                d_ff=int(_pick(raw, "d_ff")), vocab=int(_pick(raw, "vocab")),
                rope_theta=theta, eps=float(_pick(raw, "eps")),
                tie=bool(raw.get("tie_word_embeddings", False)),
                qk_norm=bool(raw.get("qk_norm", False)))


def load(path: Path):
    """``(raw file contents, Dims)`` for a configuration file; a ``.py``
    beside the ``.json`` with a ``dims(raw)`` function takes precedence."""
    raw = json.loads(Path(path).read_text())
    adapter = Path(path).with_suffix(".py")
    if adapter.exists():
        spec = importlib.util.spec_from_file_location(
            f"bench_config_{adapter.stem.replace('-', '_').replace('.', '_')}",
            adapter)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return raw, mod.dims(raw)
    return raw, dims(raw)
