"""twin-consistency: prove the hand-maintained serving twins op-for-op.

ROADMAP calls the per-layer ``resident_*`` and paged ``paged_*`` twins in
``models/dense.py`` / ``models/moe.py`` a *bit-identity hazard*: each one
must mirror one ``lax.scan`` iteration of its whole-tree step function, and
today nothing but end-to-end greedy-identity tests notices drift.  This
checker catches it at trace time: both sides are staged with
``jax.make_jaxpr`` on a microscopic :class:`ArchConfig`, the scan body is
extracted from the step function's jaxpr, and the two op sequences are
compared after canonicalization.

Canonicalization (the *documented* differences between a twin and its scan
body, see docs/STATIC_ANALYSIS.md):

* routing primitives are dropped — gather/scatter/dynamic-slice/reshape
  and friends.  The paged twins route K/V through block tables
  (``gather_blocks``/``scatter_blocks``) where the slot steps scatter the
  step's rows into the cache stack their scan carries
  (``write_kv_rows``); ``resident_block`` slices its layer's cache with
  ``dynamic_index_in_dim``, writes it with ``update_kv_cache`` and puts
  it back whole.  Routing
  moves bytes; it cannot change values, so it is exempt by construction.
* non-float and scalar outputs are dropped — the twins compute positions
  and masks locally (integer ops) and the MoE scan carries a scalar aux
  accumulator the twins do not.
* wrapper primitives (pjit / custom_jvp / remat / nested scans) are
  flattened into their inner equations.

Everything that remains — matmuls, norms, rope, softmax, quantize grids,
casts — must match in primitive, shape, and dtype, in order.  A twin that
adds, drops, or re-types one float op fails with the first divergence.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Any, Callable, List, Sequence, Tuple

from .base import Finding

# routing/bookkeeping primitives: move or reshape bytes, never change them.
ROUTING_PRIMS = frozenset({
    "gather", "scatter", "scatter-add", "dynamic_slice",
    "dynamic_update_slice", "broadcast_in_dim", "reshape", "concatenate",
    "squeeze", "slice", "pad", "iota", "transpose", "rev", "copy",
    "select_n",
})

Op = Tuple[str, Tuple[Tuple[Tuple[int, ...], str], ...]]


def _subjaxprs(eqn) -> List[Any]:
    """Every jaxpr nested in an equation's params (pjit, scan, custom_*…)."""
    subs: List[Any] = []
    for v in eqn.params.values():
        for cand in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(cand, "eqns"):            # Jaxpr
                subs.append(cand)
            elif hasattr(cand, "jaxpr") and hasattr(cand.jaxpr, "eqns"):
                subs.append(cand.jaxpr)          # ClosedJaxpr
    return subs


def canonical_ops(jaxpr) -> List[Op]:
    """Flatten a (Closed)Jaxpr into the comparable float-op sequence."""
    import jax.numpy as jnp
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    ops: List[Op] = []

    def rec(jx) -> None:
        for eqn in jx.eqns:
            subs = _subjaxprs(eqn)
            if subs:
                for s in subs:
                    rec(s)
                continue
            if eqn.primitive.name in ROUTING_PRIMS:
                continue
            outs = []
            for var in eqn.outvars:
                aval = var.aval
                if not hasattr(aval, "dtype") or not hasattr(aval, "shape"):
                    continue
                if jnp.issubdtype(aval.dtype, jnp.floating) and aval.ndim:
                    outs.append((tuple(aval.shape), str(aval.dtype)))
            if outs:
                ops.append((eqn.primitive.name, tuple(outs)))

    rec(jaxpr)
    return ops


def scan_body(closed) -> Any:
    """The inner jaxpr of the (first) ``scan`` equation — the layer body."""

    def find(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "scan":
                return eqn.params["jaxpr"]
            for s in _subjaxprs(eqn):
                got = find(s)
                if got is not None:
                    return got
        return None

    got = find(closed.jaxpr if hasattr(closed, "jaxpr") else closed)
    if got is None:
        raise ValueError("no scan equation found — step function changed "
                         "shape; update repro.analysis.twins")
    return got


def diff_ops(ref: Sequence[Op], twin: Sequence[Op]) -> str:
    """Empty string when identical, else a first-divergence description."""
    for i, (a, b) in enumerate(zip(ref, twin)):
        if a != b:
            return (f"op {i}: scan body has {a[0]}{list(a[1])} but twin "
                    f"has {b[0]}{list(b[1])}")
    if len(ref) != len(twin):
        longer, who = (ref, "scan body") if len(ref) > len(twin) \
            else (twin, "twin")
        extra = longer[min(len(ref), len(twin))]
        return (f"length {len(ref)} vs {len(twin)}: {who} additionally "
                f"computes {extra[0]}{list(extra[1])}")
    return ""


# ----------------------------------------------------------- pair builders

@dataclasses.dataclass(frozen=True)
class TwinPair:
    """One contract: ``twin`` must mirror ``ref``'s scan body op-for-op."""

    name: str
    ref_ops: Callable[[], List[Op]]
    twin_ops: Callable[[], List[Op]]
    twin_obj: Any                        # for file:line of the finding


def _tiny_cfg(family: str):
    from repro.configs.base import ArchConfig, MoEConfig
    moe = MoEConfig(num_experts=4, top_k=2) if family == "moe" else None
    return ArchConfig(name=f"lint-{family}", family=family, n_layers=2,
                      d_model=16, n_heads=2, n_kv_heads=1, d_ff=32,
                      vocab=64, head_dim=8, moe=moe,
                      source="twin-consistency lint config")


def twin_pairs(family: str) -> List[TwinPair]:
    """The five contracts for one model family ('dense' | 'moe')."""
    import jax
    import jax.numpy as jnp
    from repro.models import dense
    mod = dense if family == "dense" else __import__(
        "repro.models.moe", fromlist=["moe"])
    cfg = _tiny_cfg(family)

    B, S_CHUNK, MAX_LEN, BS = 2, 4, 8, 4      # MAX_LEN == MB * BS (MB=2)
    params = mod.init(cfg, jax.random.PRNGKey(0))
    stack = dense._layer_stack(params)
    lp0 = {k: v[0] for k, v in stack.items()}
    xdt = params["embed"].dtype

    def x_at(s):
        return jnp.zeros((B, s, cfg.d_model), xdt)

    tokens = jnp.zeros((B, S_CHUNK), jnp.int32)
    token1 = jnp.zeros((B, 1), jnp.int32)
    cache = dense.init_cache(cfg, B, MAX_LEN)
    pool = dense.init_kv_pool(cfg, n_blocks=B * 2 + 1, block_size=BS)
    bt = jnp.arange(1, B * 2 + 1, dtype=jnp.int32).reshape(B, 2)
    posv = jnp.zeros((B,), jnp.int32)         # per-slot positions

    def body_ops(fn, *args, **kw):
        return lambda: canonical_ops(
            scan_body(jax.make_jaxpr(lambda: fn(*args, **kw))()))

    def whole_ops(fn, *args, **kw):
        return lambda: canonical_ops(
            jax.make_jaxpr(lambda: fn(*args, **kw))())

    pairs = [
        TwinPair(
            f"{family}:forward-collect vs resident_prefill_block",
            body_ops(mod.forward, cfg, params, tokens, collect_cache=True),
            whole_ops(mod.resident_prefill_block, cfg, lp0, x_at(S_CHUNK),
                      positions=jnp.arange(S_CHUNK)),
            mod.resident_prefill_block),
        TwinPair(
            f"{family}:decode_step vs resident_block (S=1)",
            body_ops(mod.decode_step, cfg, params, token1, cache, posv),
            whole_ops(mod.resident_block, cfg, lp0, x_at(1), cache, 0, posv),
            mod.resident_block),
        TwinPair(
            f"{family}:prefill_chunk vs resident_block (S={S_CHUNK})",
            body_ops(mod.prefill_chunk, cfg, params, tokens, cache, posv),
            whole_ops(mod.resident_block, cfg, lp0, x_at(S_CHUNK), cache, 0,
                      posv),
            mod.resident_block),
        TwinPair(
            f"{family}:decode_step vs paged_decode_step (kv16)",
            body_ops(mod.decode_step, cfg, params, token1, cache, posv),
            body_ops(mod.paged_decode_step, cfg, params, token1, pool, bt,
                     posv),
            mod.paged_decode_step),
        TwinPair(
            f"{family}:prefill_chunk vs paged_prefill_chunk (kv16)",
            body_ops(mod.prefill_chunk, cfg, params, tokens, cache, posv),
            body_ops(mod.paged_prefill_chunk, cfg, params, tokens, pool, bt,
                     posv),
            mod.paged_prefill_chunk),
    ]
    return pairs


def compare_pair(pair: TwinPair) -> str:
    """Empty string when the contract holds, else the divergence message."""
    return diff_ops(pair.ref_ops(), pair.twin_ops())


def _location(obj) -> Tuple[str, int]:
    import inspect
    try:
        file = Path(inspect.getsourcefile(obj)).resolve()
        line = inspect.getsourcelines(obj)[1]
        from .base import REPO_ROOT
        return str(file.relative_to(REPO_ROOT)), line
    except (TypeError, OSError, ValueError):
        return "<unknown>", 0


def check(root: Path) -> List[Finding]:
    findings: List[Finding] = []
    for family in ("dense", "moe"):
        try:
            pairs = twin_pairs(family)
        except Exception as e:                    # noqa: BLE001 — surface,
            findings.append(Finding(               # never silently skip
                file=f"src/repro/models/{family}.py", line=1,
                rule="twin-consistency",
                message=f"checker could not stage {family} pairs: {e!r}",
                symbol=family))
            continue
        for pair in pairs:
            try:
                msg = compare_pair(pair)
            except Exception as e:                # noqa: BLE001
                file, line = _location(pair.twin_obj)
                findings.append(Finding(
                    file=file, line=line, rule="twin-consistency",
                    message=f"[{pair.name}] trace failed: {e!r}",
                    symbol=pair.name))
                continue
            if msg:
                file, line = _location(pair.twin_obj)
                findings.append(Finding(
                    file=file, line=line, rule="twin-consistency",
                    message=f"[{pair.name}] {msg}", symbol=pair.name))
    return findings
