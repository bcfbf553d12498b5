"""Mixture-of-Experts layers + the dbrx / qwen2-moe decoder families.

Dispatch design (TPU/JAX-native, see DESIGN.md §2): the router runs under plain pjit
(replicated over the model axis — cheap), while dispatch + expert compute run inside a
``shard_map`` over the whole mesh: every model-rank holds ``E_loc = E / |model|``
experts and all locally-resident tokens, gathers the tokens routed to its experts into
an ``(E_loc, C, D)`` capacity buffer (sort-free: one-hot cumsum positions + index
scatter, so the HLO is gather/scatter + bmm, no GSPMD surprises), and the per-rank
partial outputs are combined with a single ``psum`` over the model axis — the same
collective footprint as a Megatron TP MLP.  Capacity overflow drops tokens (GShard
semantics, ``capacity_factor`` controls the drop rate).

When no mesh context is installed (CPU smoke tests) the identical dispatch runs with
``E_loc = E`` on one device.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MoEConfig
from .layers import (Schema, Spec, deq, init_params, matmul, rms_norm,
                     softmax_xent, swiglu)
from . import dense


# ----------------------------------------------------------------- EP mesh context

@dataclasses.dataclass(frozen=True)
class EPContext:
    """Installed by the distribution layer; models stay mesh-agnostic without it."""
    mesh: Any
    model_axis: str = "model"
    data_axes: Tuple[str, ...] = ("data",)
    batch_sharded: bool = True     # False for tiny-batch decode (batch replicated)


_EP_CTX: list = [None]


def set_ep_context(ctx: Optional[EPContext]) -> None:
    _EP_CTX[0] = ctx


def get_ep_context() -> Optional[EPContext]:
    return _EP_CTX[0]


# ------------------------------------------------------------------------ dispatch

def _capacity(n_tokens: int, top_k: int, n_experts: int, cf: float) -> int:
    c = int(-(-n_tokens * top_k * cf // n_experts))  # ceil
    return max(4, -(-c // 4) * 4)                    # multiple of 4, >= 4


def _dispatch_compute(x2d: jax.Array, gates: jax.Array, idx: jax.Array,
                      w_gate: Any, w_up: Any, w_down: Any,
                      e0: jax.Array, E_loc: int, C: int) -> jax.Array:
    """Local-expert dispatch + compute.  x2d: (N, D); gates/idx: (N, K).

    Returns this rank's partial output (N, D) (zeros for tokens whose experts live on
    other ranks or that overflowed capacity).
    """
    N, D = x2d.shape
    K = idx.shape[-1]
    out = jnp.zeros((N, D), x2d.dtype)
    # slot assignment across ALL K choices at once so capacity is shared correctly
    eid = idx.reshape(-1)                                   # (N*K,) global expert ids
    local = (eid >= e0) & (eid < e0 + E_loc)
    el = jnp.where(local, eid - e0, E_loc)                  # E_loc = overflow bucket
    oh = jax.nn.one_hot(el, E_loc + 1, dtype=jnp.int32)     # (N*K, E_loc+1) small
    pos = (jnp.cumsum(oh, axis=0) - oh).max(axis=-1, initial=0, where=oh > 0)
    pos = jnp.where(local, pos, C)
    keep = local & (pos < C)
    slot = jnp.where(keep, el * C + pos, E_loc * C)         # last slot = trash

    tok = jnp.arange(N * K, dtype=jnp.int32) // K
    tok_for_slot = jnp.zeros((E_loc * C + 1,), jnp.int32).at[slot].set(tok, mode="drop")
    valid = jnp.zeros((E_loc * C + 1,), x2d.dtype).at[slot].set(1.0, mode="drop")

    buf = jnp.take(x2d, tok_for_slot[:-1], axis=0)          # (E_loc*C, D) gather
    buf = (buf * valid[:-1, None]).reshape(E_loc, C, D)

    h = jnp.einsum("ecd,edf->ecf", buf, deq(w_gate, x2d.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, deq(w_up, x2d.dtype))
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, deq(w_down, x2d.dtype))
    y_flat = jnp.concatenate([y.reshape(E_loc * C, D),
                              jnp.zeros((1, D), y.dtype)], axis=0)

    # combine one top-k choice at a time to bound live memory at (N, D)
    slot_nk = slot.reshape(N, K)
    keep_nk = keep.reshape(N, K)
    for k in range(K):
        contrib = jnp.take(y_flat, slot_nk[:, k], axis=0)
        g = (gates[:, k] * keep_nk[:, k]).astype(x2d.dtype)
        out = out + contrib * g[:, None]
    return out


def _ep_body(x: jax.Array, gates: jax.Array, idx: jax.Array,
             w_gate, w_up, w_down, *, model_axis: str, E_loc: int, C: int,
             psum_axes: Tuple[str, ...] = ()):
    """psum_axes: extra axes to reduce over — the weight-stationary serving
    layout shards the expert FFN's hidden dim over the data axes (x is
    replicated there), so partial outputs sum over (model, *data)."""
    B, S, D = x.shape
    e0 = jax.lax.axis_index(model_axis) * E_loc
    out = _dispatch_compute(x.reshape(B * S, D), gates.reshape(B * S, -1),
                            idx.reshape(B * S, -1), w_gate, w_up, w_down,
                            e0, E_loc, C)
    return jax.lax.psum(out.reshape(B, S, D), (model_axis,) + tuple(psum_axes))


def moe_mlp(x: jax.Array, wts: Dict[str, Any], mcfg: MoEConfig, n_experts_padded: int,
            ) -> Tuple[jax.Array, jax.Array]:
    """MoE feed-forward.  Returns (y, load_balance_aux)."""
    B, S, D = x.shape
    E, K = n_experts_padded, mcfg.top_k
    logits = matmul(x, wts["router"]).astype(jnp.float32)       # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, K)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balance aux: E * sum_e f_e * p_e
    me = probs.mean(axis=(0, 1))                                # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (B * S * K)
    aux = E * jnp.sum(me * ce)

    ctx = get_ep_context()
    if ctx is None:
        C = _capacity(B * S, K, E, mcfg.capacity_factor)
        y = _dispatch_compute(
            x.reshape(B * S, D), gates.reshape(-1, K), idx.reshape(-1, K),
            wts["w_gate"], wts["w_up"], wts["w_down"],
            jnp.int32(0), E, C).reshape(B, S, D)
    else:
        mesh = ctx.mesh
        msize = mesh.shape[ctx.model_axis]
        assert E % msize == 0, (E, msize)
        E_loc = E // msize
        dsize = 1
        for a in ctx.data_axes:
            dsize *= mesh.shape[a]
        B_loc = B // dsize if ctx.batch_sharded else B
        C = _capacity(B_loc * S, K, E, mcfg.capacity_factor)
        P = jax.sharding.PartitionSpec
        bspec = (tuple(ctx.data_axes) if ctx.batch_sharded else None)
        # weight-stationary serving: x is replicated over the data axes, so
        # the expert FFN hidden dim shards over them and the combine psums
        # over (model, *data) — expert weights never cross the wire.
        stationary = not ctx.batch_sharded and bool(ctx.data_axes)
        f_axes = tuple(ctx.data_axes) if stationary else None
        body = partial(_ep_body, model_axis=ctx.model_axis, E_loc=E_loc, C=C,
                       psum_axes=f_axes or ())

        def wspec(w, f_dim):
            spec = [None, None, None]
            spec[0] = ctx.model_axis
            if f_axes:
                spec[f_dim] = f_axes
            if isinstance(w, tuple) and hasattr(w, "_fields"):
                return type(w)(P(*spec), P(), P())
            return P(*spec)

        y = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(bspec, None, None), P(bspec, None, None),
                      P(bspec, None, None), wspec(wts["w_gate"], 2),
                      wspec(wts["w_up"], 2), wspec(wts["w_down"], 1)),
            out_specs=P(bspec, None, None),
            check_vma=False,
        )(x, gates, idx, wts["w_gate"], wts["w_up"], wts["w_down"])

    if mcfg.shared_experts:
        y = y + swiglu(rm_identity(x), wts["shared_w_gate"], wts["shared_w_up"],
                       wts["shared_w_down"])
    return y, aux


def rm_identity(x):  # placeholder for shared-expert input (already normed upstream)
    return x


# ------------------------------------------------------- decoder family (dbrx/qwen2)

def _padded_experts(cfg: ArchConfig, multiple: int = 16) -> int:
    return cfg.moe.padded_experts(multiple)


def moe_block_schema(prefix: str, L: int, D: int, F: int, mcfg: MoEConfig, Ep: int,
                     resid: float) -> Schema:
    s: Schema = {
        f"{prefix}/router": Spec((L, D, Ep), ("layers", "embed", "expert"), 0.02,
                                 jnp.float32),
        f"{prefix}/w_gate": Spec((L, Ep, D, F),
                                 ("layers", "expert", "expert_embed", "expert_mlp")),
        f"{prefix}/w_up": Spec((L, Ep, D, F),
                               ("layers", "expert", "expert_embed", "expert_mlp")),
        f"{prefix}/w_down": Spec((L, Ep, F, D),
                                 ("layers", "expert", "expert_mlp", "expert_embed"),
                                 resid),
    }
    if mcfg.shared_experts:
        Fs = F * mcfg.shared_experts
        s[f"{prefix}/shared_w_gate"] = Spec((L, D, Fs), ("layers", "embed", "mlp"))
        s[f"{prefix}/shared_w_up"] = Spec((L, D, Fs), ("layers", "embed", "mlp"))
        s[f"{prefix}/shared_w_down"] = Spec((L, Fs, D), ("layers", "mlp", "embed"), resid)
    return s


def schema(cfg: ArchConfig) -> Schema:
    """dbrx / qwen2-moe: dense attention + MoE feed-forward every layer."""
    L, D = cfg.n_layers, cfg.d_model
    Ep = _padded_experts(cfg)
    resid = 0.02 / (2 * L) ** 0.5
    s = dense.schema(cfg)
    for k in ["layers/w_gate", "layers/w_up", "layers/w_down"]:
        del s[k]
    s.update(moe_block_schema("layers/moe", L, D, cfg.d_ff, cfg.moe, Ep, resid))
    return s


def init(cfg: ArchConfig, key: jax.Array) -> Dict[str, jax.Array]:
    return init_params(schema(cfg), key)


def _moe_wts(lp: Dict[str, Any]) -> Dict[str, Any]:
    return {k.split("/", 1)[1]: v for k, v in lp.items() if k.startswith("moe/")}


def _mlp(cfg: ArchConfig, lp, h):
    """The MoE feed-forward as ``(y, load_balance_aux)``: the MLP this
    family hands the dense block."""
    return moe_mlp(h, _moe_wts(lp), cfg.moe, _padded_experts(cfg))


# The dense family's block, step pieces and cached layer scan with the MoE
# MLP (see dense.py §step pieces).  The cached steps drop the load-balance
# aux; serving never reads it.
#
# NOTE on dispatch capacity: the GShard capacity ``C`` is a function of the
# number of tokens in flight, so a chunk and a full-prompt prefill route
# identically only while no expert overflows — serve MoE with a
# ``capacity_factor`` that admits the worst case (``>= E / top_k``) when
# bit-reproducibility across batch packings matters.
#
# Under `CompressedResidentWeights(fused=True)` the 2-D attention weights
# arrive as FusedQT payload handles (decoded inside `layers.matmul`); the
# (L, E, D, F) expert stacks fail the fused tile contract (not a stacked
# matrix) and automatically stay on the unfused per-layer decode path —
# the per-tensor fallback `tests/differential/` pins.

embed = dense.embed
head = dense.head
step_positions = dense.step_positions
prefill_layer = partial(dense.prefill_layer, mlp=_mlp)
cached_layer = partial(dense.cached_layer, mlp=_mlp)
decode_step = partial(dense.decode_step, mlp=_mlp)
prefill_chunk = partial(dense.prefill_chunk, mlp=_mlp)


def forward(cfg: ArchConfig, params, tokens, *, unroll: int = 1, q_block: int = 0,
            remat: bool = False, collect_cache: bool = False):
    B, S = tokens.shape
    x = embed(cfg, params, tokens)
    positions = jnp.arange(S)
    stack = dense._layer_stack(params)

    def body(carry, lp):
        x, aux_sum = carry
        x, kv, aux = prefill_layer(cfg, lp, x, positions, q_block=q_block,
                                   unroll=unroll)
        return (x, aux_sum + aux), kv if collect_cache else None

    fn = jax.checkpoint(body) if remat else body
    (x, aux), caches = jax.lax.scan(fn, (x, jnp.float32(0.0)), stack, unroll=unroll)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, caches, aux / cfg.n_layers


def loss_fn(cfg: ArchConfig, params, batch, *, unroll: int = 1, q_block: int = 0,
            remat: bool = True, aux_coef: float = 0.01) -> jax.Array:
    tokens = batch["tokens"]
    inp, labels = tokens[:, :-1], tokens[:, 1:]
    x, _, aux = forward(cfg, params, inp, unroll=unroll, q_block=q_block, remat=remat)
    return softmax_xent(dense.logits_fn(cfg, params, x), labels, cfg.vocab) \
        + aux_coef * aux


init_cache = dense.init_cache
cache_specs = dense.cache_specs
init_kv_pool = dense.init_kv_pool


def prefill(cfg: ArchConfig, params, tokens, *, max_len: Optional[int] = None,
            unroll: int = 1, q_block: int = 0):
    B, S = tokens.shape
    max_len = max_len or S
    x, caches, _ = forward(cfg, params, tokens, unroll=unroll, q_block=q_block,
                           collect_cache=True)
    k, v = caches
    pad = [(0, 0), (0, 0), (0, max_len - S), (0, 0), (0, 0)]
    cache = {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}
    return dense.logits_fn(cfg, params, x[:, -1:, :]), cache
