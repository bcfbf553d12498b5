"""One cell's run through the program under test: set-up, the closed loop,
and the numbers the metric readers take.

Set-up makes the weights from the seed (``reference/<family>.py``, on the
device in one jitted call), then runs the launcher's own steps
(``repro.launch.serve``): ``compress`` (quantize + Huffman-encode on the
host) and ``load`` (the jit decoder streams the container into resident
uint8 weights).  It builds one ``ContinuousEngine`` and starts every
client at once; the closed loop runs until every client has its first
token and one request has ended, which compiles every program the traffic
uses (the slot release included) and drains the start-up burst of
prefills.  The measured phases then drive the same engine and
clients without a pause.

Tokens are stamped on the host clock when they become visible, after the
``ce.step()`` that produced them.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import sys
import time
from typing import Dict, List, Optional, get_args, get_type_hints

import numpy as np

import loadgen
import work
from cell import Cell


@dataclasses.dataclass
class Phase:
    """What one stretch of the closed loop did."""
    t0: float = 0.0
    t1: float = 0.0
    tokens: int = 0             # output tokens that became visible
    ttft: List[float] = dataclasses.field(default_factory=list)   # seconds
    itl: List[float] = dataclasses.field(default_factory=list)    # seconds
    sent: int = 0
    failed: int = 0             # requests that ended short of their length
    steps: int = 0
    decode_steps: int = 0
    decode_slots: int = 0       # requests advanced, summed over decode steps
    flops: int = 0              # model operations of the tokens it made
    decode_bytes: int = 0       # bytes its decode steps had to read
    done: List[tuple] = dataclasses.field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass
class Client:
    idx: int
    j: int = 0                  # index of the request in flight
    req: object = None
    t_send: float = 0.0
    t_last: float = 0.0
    seen: int = 0               # output tokens already stamped
    ended: int = 0              # requests that ended


def _annotate(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def arch_config(cell: Cell):
    """The program's ``ArchConfig`` for the cell's configuration: the sizes
    ``Dims`` states, and the further keyword arguments its adapter declares
    (``arch_kwargs(raw)``, ``bench/modelcfg.py``)."""
    from repro.configs.base import ArchConfig
    d = cell.dims
    kw = dict(
        name=cell.config_name, family=d.family, n_layers=d.layers,
        d_model=d.d_model, n_heads=d.heads, n_kv_heads=d.kv_heads,
        d_ff=d.d_ff, vocab=d.vocab, head_dim=d.head_dim, qk_norm=d.qk_norm,
        rope_theta=d.rope_theta, tie_embeddings=d.tie,
        source=cell.raw.get("source", ""))
    declared = getattr(cell.adapter, "arch_kwargs", None)
    if declared is not None:
        kw.update(_declared_kwargs(ArchConfig, kw, declared(cell.raw)))
    return ArchConfig(**kw)


def _declared_kwargs(cls, given: dict, declared: dict) -> dict:
    """``declared`` as keyword arguments of the dataclass ``cls``: a dict
    given for a field whose type is a dataclass (``MoEConfig``,
    ``SSMConfig``) is built into it.  A key ``cls`` does not take, or one
    ``given`` already sets from ``Dims``, is an error."""
    fields = {f.name for f in dataclasses.fields(cls)}
    hints = get_type_hints(cls)
    out = {}
    for key, value in declared.items():
        if key not in fields:
            raise TypeError(f"arch_kwargs: {cls.__name__} takes no {key!r}")
        if key in given:
            raise TypeError(f"arch_kwargs: {key!r} comes from the "
                            f"configuration's Dims, not from arch_kwargs")
        if isinstance(value, dict):
            kinds = [t for t in get_args(hints[key]) or (hints[key],)
                     if dataclasses.is_dataclass(t)]
            if not kinds:
                raise TypeError(f"arch_kwargs: {cls.__name__}.{key} is no "
                                f"dataclass; a dict cannot be built into it")
            value = kinds[0](**value)
        out[key] = value
    return out


def _to_program_layout(host: Dict[str, np.ndarray], shapes) -> None:
    """Zero-pad each tensor up to the shape the program takes (its
    vocabulary rounds up to a multiple of 512 rows), in place."""
    if set(host) != set(shapes):
        raise ValueError(f"weights {sorted(host)} are not the program's "
                         f"parameters {sorted(shapes)}")
    for name, want in shapes.items():
        have = host[name].shape
        if have == tuple(want):
            continue
        if len(have) != len(want) or any(h > w for h, w in zip(have, want)):
            raise ValueError(f"{name}: generated {have}, program takes {want}")
        host[name] = np.pad(host[name], [(0, w - h) for h, w in
                                         zip(have, want)])


RESIDENT_FLAGS = {
    # resident weights -> the launcher's flags for them
    "uint8": [],                            # QT triples, bf16 dequantize
    "float32": ["--no-quantized-serving"],  # dequantized once at load
}


def resident_weights(raw: dict) -> str:
    """How the configuration keeps its weights on the device once loaded:
    a key of ``RESIDENT_FLAGS``."""
    r = raw["serving"]["resident_weights"]
    if r not in RESIDENT_FLAGS:
        raise ValueError(f"serving.resident_weights {r!r}: not one of "
                         f"{sorted(RESIDENT_FLAGS)}")
    return r


def _compression_spec(raw: dict):
    from repro.core.quant import Granularity
    from repro.core.spec import spec_from_legacy
    c = raw["compression"]
    return spec_from_legacy(int(c["bits"]), Granularity(c["granularity"]),
                            codec=c["codec"],
                            segment_symbols=int(c["segment_symbols"]),
                            max_code_len=int(c["max_code_len"]))


class ServedCell:
    """The program serving one cell, from one seed."""

    def __init__(self, cell: Cell, seed: int, family_ref):
        self.cell = cell
        self.seed = seed
        self.ref = family_ref
        self.setup: Dict[str, float] = {}

    # ------------------------------------------------------------- set-up
    def build(self) -> None:
        """Weights, compression and load: everything before the engine."""
        import jax
        from repro.launch import serve
        from repro.models import api
        cell, d = self.cell, self.cell.dims
        self.cfg = arch_config(cell)
        # the launcher prints its progress; stdout is kept for the result
        with contextlib.redirect_stdout(sys.stderr):
            t = time.perf_counter()
            dev = self.ref.make_weights(d, self.seed)
            host = {k: np.asarray(v).astype(np.float32)
                    for k, v in dev.items()}
            del dev
            _to_program_layout(host, api.param_shapes(self.cfg))
            self.setup["init_s"] = time.perf_counter() - t

            t = time.perf_counter()
            cm = serve.compress(host, _compression_spec(cell.raw))
            self.setup["compress_s"] = time.perf_counter() - t
            self.stored = self._stored_bytes(cm, host)
            del host

            t = time.perf_counter()
            args = serve.parse_args([
                "--arch", cell.config_name, "--chunk-symbols",
                str(int(cell.raw["serving"]["decode_chunk_symbols"]))]
                + RESIDENT_FLAGS[resident_weights(cell.raw)])
            self.params, self.load_metrics = serve.load(cm, self.cfg, args)
            jax.block_until_ready(jax.tree.leaves(self.params))
            self.setup["load_s"] = time.perf_counter() - t
            del cm

    def _stored_bytes(self, cm, host) -> Dict[str, int]:
        """Bytes each tensor is stored in: its coded payload when
        quantized, float32 otherwise; the vocabulary's padding rows are
        left out."""
        d = self.cell.dims
        out = {}
        for name, w in host.items():
            if name in cm.tensors:
                b = int(cm.tensors[name].seg_nbytes.sum())
            else:
                b = int(w.nbytes)
            if name in ("embed", "lm_head"):
                b = b * d.vocab // max(w.shape[0] if name == "embed"
                                       else w.shape[1], 1)
            out[name] = b
        return out

    def start(self) -> None:
        """The engine, and every client's first request."""
        from repro.serving.batching import ContinuousEngine
        from repro.serving.engine import ServeConfig
        mix = self.cell.mix
        self.loop = loadgen.ClosedLoop(mix, self.cell.dims.vocab, self.seed)
        need = self.loop.max_cache_rows(int(mix["prefill_chunk"]))
        if need > int(mix["max_len"]):
            raise ValueError(f"traffic {self.cell.traffic_name}: the longest "
                             f"request needs {need} cache rows, max_len is "
                             f"{mix['max_len']}")
        self.ce = ContinuousEngine(
            self.cfg, self.params, ServeConfig(max_len=int(mix["max_len"])),
            n_slots=int(mix["slots"]), max_queue=self.loop.clients,
            prefill_chunk=int(mix["prefill_chunk"]),
            admit_chunks_per_step=int(mix["admit_chunks_per_step"]))
        self.clients = [Client(i) for i in range(self.loop.clients)]
        self.annotate = False
        for c in self.clients:
            self._send(c, None)

    def ramp(self) -> float:
        """Run the loop until every client has had a token and one request
        has ended; returns its seconds."""
        t = time.perf_counter()
        while min(c.ended + c.seen for c in self.clients) < 1 \
                or max(c.ended for c in self.clients) < 1:
            self._step(None)
        return time.perf_counter() - t

    # ------------------------------------------------------------ driving
    def _send(self, c: Client, phase: Optional[Phase]) -> None:
        with _annotate("bench.client", self.annotate):
            prompt, out_len = self.loop.request(c.idx, c.j)
            c.t_send = time.perf_counter()
            c.req = self.ce.submit(prompt, out_len)
            c.seen = 0
            if phase is not None:
                phase.sent += 1

    def _step(self, phase: Optional[Phase]) -> None:
        from repro.serving.batching.request import RequestState
        d, adapter = self.cell.dims, self.cell.adapter
        n0 = self.ce.n_decode_steps
        with _annotate("bench.ce_step", self.annotate):
            self.ce.step()
        now = time.perf_counter()
        decoded = kv_rows = 0
        with _annotate("bench.stamp", self.annotate):
            ended = []
            for c in self.clients:
                req = c.req
                n = len(req.output)
                new = n - c.seen
                if new == 0:
                    continue
                first = c.seen == 0
                if phase is not None:
                    phase.tokens += new
                    if first:
                        phase.ttft.append(now - c.t_send)
                        phase.flops += work.prefill_flops(d, req.prompt_len,
                                                          adapter)
                        phase.itl.extend([0.0] * (new - 1))
                    else:
                        phase.itl.append(now - c.t_last)
                        phase.itl.extend([0.0] * (new - 1))
                if new > int(first):        # the decode step advanced it
                    rows = req.prompt_len + n - 1
                    decoded += 1
                    kv_rows += rows
                    if phase is not None:
                        phase.flops += work.decode_flops(d, rows, adapter)
                c.seen, c.t_last = n, now
                if req.state is RequestState.FINISHED:
                    ended.append(c)
            if phase is not None:
                phase.steps += 1
                if self.ce.n_decode_steps > n0:
                    phase.decode_steps += 1
                    phase.decode_slots += decoded
                    phase.decode_bytes += (
                        work.decode_weight_bytes(d, self.stored, decoded,
                                                 adapter)
                        + work.kv_bytes(d, kv_rows))
        for c in ended:
            if phase is not None:
                phase.done.append((c.req.prompt.copy(), list(c.req.output)))
                phase.failed += len(c.req.output) != c.req.max_new_tokens
            c.ended += 1
            c.j += 1
            self._send(c, phase)

    def measure(self, seconds: float, *, annotate: bool = False) -> Phase:
        """Drive the loop for ``seconds``; the phase ends with the first
        step that ends past it.  Python's collector is held off meanwhile
        (what set-up left is frozen out of its scans), so no collection
        pauses land in one run's window and not in another's."""
        self.annotate = annotate
        ph = Phase()
        gc.collect()
        gc.freeze()
        gc.disable()
        try:
            ph.t0 = time.perf_counter()
            end = ph.t0 + seconds
            with _annotate("bench.window", annotate):
                while True:
                    self._step(ph)
                    ph.t1 = time.perf_counter()
                    if ph.t1 >= end:
                        break
        finally:
            gc.enable()
            gc.unfreeze()
        self.annotate = False
        return ph

    def release(self) -> None:
        """Drop the engine and the weights so the reference has the chip."""
        self.ce = self.params = None
        self.clients = []
        gc.collect()
