"""Pluggable decoder backends for the multi-stream entropy decode.

One decode *call* takes a packed stream matrix (S segments x B bytes, guard
padded), per-segment symbol counts, and a codec's decode tables, and returns
the (S, max_count) int32 symbol matrix.  Two **kernel families** cover every
registered codec (see :mod:`repro.core.codecs`, DESIGN.md §7):

* ``"prefix"`` — canonical-code LUT loop (``huffman`` and the ``raw``
  bit-packed baseline): ``core.bitstream.decode_streams`` (numpy),
  ``core.decode_jax.decode_streams_jax`` (jit),
  ``kernels.huffman_decode.decode_streams_pallas`` (TPU kernel).
* ``"tans"`` — carried-state tANS loop (``rans``):
  ``core.bitstream.decode_streams_tans``,
  ``core.decode_jax.decode_streams_tans_jax``,
  ``kernels.ans_decode.decode_streams_tans_pallas``.

This module makes the implementation choice a first-class, *named* decision
instead of an ad-hoc per-call-site import:

* ``register_backend`` / ``get_backend`` — a string-keyed registry
  (``"numpy"``, ``"jax"``, ``"pallas"``, ``"pallas-interpret"``).
* Rules, not probes — each backend states where it runs
  (:meth:`DecoderBackend.available`) and when ``auto`` may pick it
  (:meth:`DecoderBackend.auto_eligible`) as plain checks of the platform,
  with the reason in ``auto_why``.  Nothing is compiled to find out and no
  compiler error is swallowed: a backend named on a host where its kernel
  does not compile fails with the compiler's own error.
* ``auto_pick`` — the highest-priority eligible backend: the jit decoder
  when an accelerator is attached, the numpy host path otherwise.  The
  compiled Pallas kernels are never auto-picked while
  :data:`PALLAS_DECODE_LOWERS` is False (``tests/test_tpu_compile.py`` pins
  Mosaic's refusal against a described v5e), and interpret mode runs only
  when ``pallas-interpret`` is named.
* :meth:`DecoderBackend.decode_table` — codec-aware entry point: a
  :class:`repro.core.codecs.base.CodeTable` names its kernel family and
  supplies the gather arrays; the backend routes to the right loop.

The :class:`repro.core.scheduler.DecodeScheduler` drives whichever backend it
is handed; see docs/ARCHITECTURE.md §"Streaming decode" for the data flow.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Mapping, Optional

import numpy as np

from .bitstream import decode_streams, decode_streams_tans


@dataclasses.dataclass(frozen=True)
class DecoderBackend:
    """A named decode implementation + its capability probes.

    ``fns`` maps kernel family -> callable:
      ``fns["prefix"](mat, counts, lut_sym, lut_len, max_len, max_count,
      out=None)``
      ``fns["tans"](mat, counts, tab_sym, tab_bits, tab_base, table_log,
      max_count, out=None)`` — both return an (S, >=max_count) int32 ndarray.
    ``out`` is an optional preallocated int32 host buffer (the
    decode-into-buffer serving contract): the numpy family decodes straight
    into it, device-returning families (jax / pallas) copy their result into
    it — either way the caller's buffer holds the symbols on return, so a
    per-layer decode loop reuses one scratch allocation.
    ``probe`` is the rule for "can this backend run here at all?" (gates
    by-name requests); ``auto_probe`` the rule for "should auto-pick use it
    here?" — e.g. the jit decoder runs fine on CPU but is only *preferred*
    when an accelerator is attached, and the interpret fallback is runnable
    everywhere yet never auto-picked.  ``auto_why`` states that rule for
    the launcher's report.  ``priority`` orders auto-pick (higher wins).
    """

    name: str
    fns: Mapping[str, Callable[..., np.ndarray]]
    probe: Callable[[], bool]
    priority: int = 0
    auto_probe: Optional[Callable[[], bool]] = None
    auto_why: str = ""
    # the *fused* capability (decode→dequant→matmul in one pass, see
    # kernels/fused_decode_matmul.py): family -> callable
    #   fused_fns[fam](table, x, mat, scale, zero, *, seg_symbols, K, N,
    #                  bits) -> (..., N) activations
    # it runs wherever the backend does (``probe``)
    fused_fns: Optional[Mapping[str, Callable]] = None

    @property
    def fn(self) -> Callable[..., np.ndarray]:
        """Legacy alias: the prefix-family decode callable."""
        return self.fns["prefix"]

    def available(self) -> bool:
        return bool(self.probe())

    def auto_eligible(self) -> bool:
        return bool((self.auto_probe or self.probe)())

    def kernel_families(self) -> List[str]:
        return sorted(self.fns)

    def fused_available(self) -> bool:
        """Can this backend run the fused decode→dequant→matmul here?"""
        return bool(self.fused_fns) and self.available()

    def fused_families(self) -> List[str]:
        return sorted(self.fused_fns or ())

    def fused_matmul(self, table, x, mat, scale, zero, *, seg_symbols: int,
                     K: int, N: int, bits: int = 8):
        """Fused ``x @ dequant(decode(mat))`` through this backend's kernel
        (same family routing as :meth:`decode_table`)."""
        fn = (self.fused_fns or {}).get(table.kernel)
        if fn is None:
            raise RuntimeError(
                f"decoder backend {self.name!r} has no fused {table.kernel!r} "
                f"kernel (fused families: {self.fused_families()})")
        return fn(table, x, mat, scale, zero, seg_symbols=seg_symbols,
                  K=K, N=N, bits=bits)

    def decode(self, mat: np.ndarray, counts: np.ndarray, lut_sym: np.ndarray,
               lut_len: np.ndarray, *, max_len: int,
               max_count: Optional[int] = None,
               out: Optional[np.ndarray] = None) -> np.ndarray:
        """Prefix-family decode (the pre-codec-registry contract, kept for
        direct callers); codec-aware callers use :meth:`decode_table`."""
        counts = np.asarray(counts, dtype=np.int64)
        mc = int(counts.max(initial=0)) if max_count is None else int(max_count)
        res = self.fns["prefix"](mat, counts, lut_sym, lut_len, max_len, mc,
                                 out=out)
        return np.asarray(res)[:, :mc] if mc else np.asarray(res)

    def decode_table(self, table, mat: np.ndarray, counts: np.ndarray, *,
                     max_count: Optional[int] = None,
                     out: Optional[np.ndarray] = None) -> np.ndarray:
        """Decode streams encoded under ``table`` (a codecs.CodeTable): the
        table names its kernel family and supplies the gather arrays.
        ``out`` is the optional decode-into-preallocated-buffer contract
        shared by both kernel families (see the class docstring)."""
        try:
            fn = self.fns[table.kernel]
        except KeyError:
            raise RuntimeError(
                f"decoder backend {self.name!r} has no {table.kernel!r} "
                f"kernel (families: {self.kernel_families()})") from None
        counts = np.asarray(counts, dtype=np.int64)
        mc = int(counts.max(initial=0)) if max_count is None else int(max_count)
        a = table.decode_arrays()
        if table.kernel == "prefix":
            res = fn(mat, counts, a["lut_sym"], a["lut_len"],
                     table.peek_bits, mc, out=out)
        elif table.kernel == "tans":
            res = fn(mat, counts, a["tab_sym"], a["tab_bits"], a["tab_base"],
                     table.table_log, mc, out=out)
        else:
            raise RuntimeError(f"unknown kernel family {table.kernel!r}")
        return np.asarray(res)[:, :mc] if mc else np.asarray(res)


_REGISTRY: Dict[str, DecoderBackend] = {}


def register_backend(backend: DecoderBackend) -> DecoderBackend:
    _REGISTRY[backend.name] = backend
    return backend


def backend_names() -> List[str]:
    return sorted(_REGISTRY)


def available_backends() -> List[str]:
    return [n for n in backend_names() if _REGISTRY[n].available()]


def auto_pick() -> DecoderBackend:
    """Highest-priority backend whose auto rule holds on this host."""
    ranked = sorted(_REGISTRY.values(), key=lambda b: -b.priority)
    for b in ranked:
        if b.auto_eligible():
            return b
    return _REGISTRY["numpy"]    # always available by construction


def auto_pick_reason() -> str:
    """Which backend ``auto`` picks here and why: the picked backend's rule
    and the rule of every higher-priority backend it passed over."""
    import jax
    picked = auto_pick()
    skipped = [f"{b.name} skipped ({b.auto_why})"
               for b in sorted(_REGISTRY.values(), key=lambda b: -b.priority)
               if b.priority > picked.priority]
    return "; ".join([f"platform {jax.default_backend()}", *skipped,
                      f"{picked.name} picked ({picked.auto_why})"])


def get_backend(name: Optional[str] = None) -> DecoderBackend:
    """Resolve a backend by name; ``None`` / ``"auto"`` -> the rule-based
    pick (:func:`auto_pick`).

    Asking for a backend that cannot run here raises so misconfiguration is
    loud.
    """
    if name is None or name == "auto":
        return auto_pick()
    try:
        b = _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown decoder backend {name!r}; "
                       f"registered: {backend_names()}") from None
    if not b.available():
        raise RuntimeError(f"decoder backend {name!r} is not available on "
                           f"this host (available: {available_backends()})")
    return b


def _fill_out(out, res, rows, max_count):
    """Decode-into-buffer fallback for kernels that return fresh (possibly
    bucket-padded) arrays: copy the ``rows`` real streams' symbols into the
    caller's buffer and return the written view.  Same contract — including
    the undersized-buffer ValueError — as the numpy family's in-place path
    (``bitstream._decode_out``); ``rows`` is the pre-bucketing stream count,
    so bucket-padding rows are never copied and never required to fit."""
    if out is None:
        return res
    if out.dtype != np.int32 or out.shape[0] < rows \
            or out.shape[1] < max_count:
        raise ValueError(
            f"decode out buffer {out.dtype}{out.shape} too small for "
            f"({rows}, {max_count}) int32")
    res = np.asarray(res)
    out[:rows, :max_count] = res[:rows, :max_count]
    return out[:rows, :max_count]


# ---------------------------------------------------------- fused capability
def _fused_ref(table, x, mat, scale, zero, *, seg_symbols, K, N, bits=8):
    """Host-decode fused oracle (the numpy backend's 'fused' path — decode
    on host, dequant+dot through the exact serving ops)."""
    import jax.numpy as jnp
    from repro.kernels.ref import fused_decode_matmul_ref
    return fused_decode_matmul_ref(jnp.asarray(x), mat, table, scale, zero,
                                   seg_symbols=seg_symbols, K=K, N=N)


def _fused_impl(impl: str):
    def fn(table, x, mat, scale, zero, *, seg_symbols, K, N, bits=8):
        import jax.numpy as jnp
        from repro.kernels.fused_decode_matmul import (build_fused_qt,
                                                       fused_decode_matmul)
        fq = build_fused_qt(table, mat, scale, zero, seg_symbols=seg_symbols,
                            K=K, N=N, bits=bits, impl=impl)
        return fused_decode_matmul(jnp.asarray(x), fq)
    return fn


# What the TPU compiler accepts, as a rule in code rather than a probe that
# swallows compiler errors.  Mosaic refuses the per-lane LUT gathers of every
# Pallas decode kernel (plain and fused, prefix and tANS) on v5e, while the
# jit decoders compile; tests/test_tpu_compile.py pins both sides against a
# described v5e topology (the Pallas refusals as strict xfails, so a kernel
# that starts to lower fails that test until this flag is flipped).
PALLAS_DECODE_LOWERS = False


def on_tpu() -> bool:
    import jax
    return jax.default_backend() == "tpu"


def pallas_auto_eligible() -> bool:
    """Auto rule shared by the decode backend and the fused-impl default."""
    return PALLAS_DECODE_LOWERS and on_tpu()


# ------------------------------------------------------------------ numpy
def _numpy_decode(mat, counts, lut_sym, lut_len, max_len, max_count,
                  out=None):
    return decode_streams(mat, counts, lut_sym, lut_len, max_len, out=out)


def _numpy_decode_tans(mat, counts, tab_sym, tab_bits, tab_base, table_log,
                       max_count, out=None):
    return decode_streams_tans(mat, counts, tab_sym, tab_bits, tab_base,
                               table_log, out=out)


register_backend(DecoderBackend(
    name="numpy",
    fns={"prefix": _numpy_decode, "tans": _numpy_decode_tans},
    probe=lambda: True, priority=0,
    auto_why="the host decoder, auto when no backend above qualifies",
    fused_fns={"prefix": _fused_ref, "tans": _fused_ref}))


# -------------------------------------------------------------------- jax
def _jax_accelerated() -> bool:
    import jax
    return jax.default_backend() != "cpu"


def _jax_decode(mat, counts, lut_sym, lut_len, max_len, max_count, out=None):
    import jax.numpy as jnp
    from .decode_jax import bucket_streams, decode_streams_jax
    rows = mat.shape[0]
    mat, counts, mc = bucket_streams(mat, counts, max_count)
    res = decode_streams_jax(jnp.asarray(mat), jnp.asarray(counts, jnp.int32),
                             jnp.asarray(lut_sym), jnp.asarray(lut_len),
                             max_len=max_len, max_count=mc)
    return _fill_out(out, res, rows, max_count)


def _jax_decode_tans(mat, counts, tab_sym, tab_bits, tab_base, table_log,
                     max_count, out=None):
    import jax.numpy as jnp
    from .decode_jax import bucket_streams, decode_streams_tans_jax
    rows = mat.shape[0]
    mat, counts, mc = bucket_streams(mat, counts, max_count)
    res = decode_streams_tans_jax(
        jnp.asarray(mat), jnp.asarray(counts, jnp.int32),
        jnp.asarray(tab_sym), jnp.asarray(tab_bits), jnp.asarray(tab_base),
        table_log=table_log, max_count=mc)
    return _fill_out(out, res, rows, max_count)


register_backend(DecoderBackend(
    name="jax",
    fns={"prefix": _jax_decode, "tans": _jax_decode_tans},
    probe=lambda: True, priority=10, auto_probe=_jax_accelerated,
    auto_why="auto when an accelerator is attached",
    fused_fns={"prefix": _fused_impl("jax"), "tans": _fused_impl("jax")}))


# ----------------------------------------------------------------- pallas
def _pallas_decode(interpret: bool):
    def fn(mat, counts, lut_sym, lut_len, max_len, max_count, out=None):
        import jax.numpy as jnp
        from repro.kernels.huffman_decode import decode_streams_pallas
        from .decode_jax import bucket_streams
        rows = mat.shape[0]
        mat, counts, mc = bucket_streams(mat, counts, max_count)
        res = decode_streams_pallas(
            jnp.asarray(mat), jnp.asarray(counts, jnp.int32),
            jnp.asarray(lut_sym), jnp.asarray(lut_len),
            max_len=max_len, max_count=mc, interpret=interpret)
        return _fill_out(out, res, rows, max_count)
    return fn


def _pallas_decode_tans(interpret: bool):
    def fn(mat, counts, tab_sym, tab_bits, tab_base, table_log, max_count,
           out=None):
        import jax.numpy as jnp
        from repro.kernels.ans_decode import decode_streams_tans_pallas
        from .decode_jax import bucket_streams
        rows = mat.shape[0]
        mat, counts, mc = bucket_streams(mat, counts, max_count)
        res = decode_streams_tans_pallas(
            jnp.asarray(mat), jnp.asarray(counts, jnp.int32),
            jnp.asarray(tab_sym), jnp.asarray(tab_bits),
            jnp.asarray(tab_base),
            table_log=table_log, max_count=mc, interpret=interpret)
        return _fill_out(out, res, rows, max_count)
    return fn


register_backend(DecoderBackend(
    name="pallas",
    fns={"prefix": _pallas_decode(interpret=False),
         "tans": _pallas_decode_tans(interpret=False)},
    # compiled Pallas exists only on a TPU; named there, a kernel Mosaic
    # refuses raises the compiler's own error at its first decode
    probe=on_tpu, priority=20, auto_probe=pallas_auto_eligible,
    auto_why="auto only on a TPU where Mosaic lowers its per-lane LUT "
             "gathers (PALLAS_DECODE_LOWERS)",
    fused_fns={"prefix": _fused_impl("pallas"),
               "tans": _fused_impl("pallas")}))

# Interpret mode re-runs the kernel's Python trace per symbol step — orders of
# magnitude slower than the numpy path.  Explicit opt-in only (never auto).
register_backend(DecoderBackend(
    name="pallas-interpret",
    fns={"prefix": _pallas_decode(interpret=True),
         "tans": _pallas_decode_tans(interpret=True)},
    probe=lambda: True, priority=-10, auto_probe=lambda: False,
    auto_why="never auto: interpret mode runs only when named",
    fused_fns={"prefix": _fused_impl("pallas-interpret"),
               "tans": _fused_impl("pallas-interpret")}))
