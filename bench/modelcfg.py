"""Read a configuration file (``bench/configs/<name>.json``) into the sizes
the harness and the reference use.

Configuration files keep the key names of the model's own published
``config.json``; those differ between model families (``num_hidden_layers``
against ``num_layers``, ``rope_theta`` against ``rope_ratio``), so each size
is looked up under every name it is published as.

A configuration whose block ``Dims``' shared sizes cannot state brings an
adapter, ``bench/configs/<name>.py`` beside its file, as a new file.  Each
of its functions is optional and is found by name:

- ``dims(raw) -> Dims``: the served sizes, in place of ``dims`` here; the
  sizes the family adds (experts, top-k, expert widths, ...) go into
  ``Dims.family_sizes`` (``family_sizes(dict)``);
- ``arch_kwargs(raw) -> dict``: further keyword arguments of the program's
  ``ArchConfig``, as plain values; a dict for a field whose type is a
  dataclass (``moe``: ``MoEConfig``) is built into it
  (``bench/serve_cell.arch_config``);
- ``matmul_flops(d)``, ``decode_weight_bytes(d, stored, active)``: the
  family's own work counts (``bench/work.py``, whose sparse-expert counts
  an adapter can name).

A file with no adapter gives the ``Dims`` of ``dims`` here, with no family
sizes, the ``ArchConfig`` of those sizes and the dense counts.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Optional, Tuple

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
    # the sizes a family adds, as sorted (name, value) pairs: hashable, so
    # Dims stays a static argument of the references' jitted functions
    family_sizes: Tuple[Tuple[str, object], ...] = ()

    def size(self, name: str):
        """One of ``family_sizes``; a missing one is an error."""
        sizes = dict(self.family_sizes)
        if name not in sizes:
            raise KeyError(f"{self.family}: no family size {name!r} "
                           f"(has {sorted(sizes)})")
        return sizes[name]


def family_sizes(sizes: dict) -> Tuple[Tuple[str, object], ...]:
    """``sizes`` in the form ``Dims.family_sizes`` keeps them."""
    return tuple(sorted(sizes.items()))


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


def adapter(path: Path) -> Optional[ModuleType]:
    """The ``.py`` beside a configuration file, imported, or None."""
    file = Path(path).with_suffix(".py")
    if not file.exists():
        return None
    spec = importlib.util.spec_from_file_location(
        f"bench_config_{file.stem.replace('-', '_').replace('.', '_')}", file)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(path: Path):
    """``(raw file contents, Dims, adapter or None)`` for a configuration
    file; the adapter's ``dims(raw)``, where it has one, takes precedence."""
    raw = json.loads(Path(path).read_text())
    mod = adapter(path)
    return raw, getattr(mod, "dims", dims)(raw), mod
