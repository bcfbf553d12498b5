"""Compile each cell's serving programs for a described TPU v5e, without
the chip, and print what the compiler says of their memory.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [workload ...]

For every cell (default: all of ``BENCHMARK.json``) it lowers the program's
own jitted steps at the cell's shapes, on one chip of a described
``v5e:2x2``: the ``(1, prefill_chunk)`` chunked prefill into a scratch
cache, the ``(slots, 1)`` decode over the slot cache, and the head alone
(for a tied head, the dequantized embedding's transpose), then prints
``memory_analysis()``.  A program the chip's compiler refuses fails here.
No numbers come out of it: nothing runs.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import cell as cells  # noqa: E402


def param_structs(cfg, sharding, resident: str):
    """Shape stand-ins of the resident weights: with ``resident`` "uint8",
    QT triples with one (scale, zero) per index of axis 0 and float32 norm
    weights; with "float32", every weight dense in float32."""
    import jax
    import jax.numpy as jnp
    from repro.core.spec import quantizable_shape
    from repro.models import api
    from repro.models.layers import QT

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    out = {}
    for name, shape in api.param_shapes(cfg).items():
        if resident == "uint8" and quantizable_shape(name, shape):
            ch = (shape[0],) + (1,) * (len(shape) - 1)
            out[name] = QT(sds(shape, jnp.uint8), sds(ch, jnp.float32),
                           sds(ch, jnp.float32))
        else:
            out[name] = sds(shape, jnp.float32)
    return out


def rehearse(workload: str, device) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.models import dense
    from repro.serving.engine import ServeConfig, ServeSteps
    from serve_cell import arch_config, resident_weights
    cell = cells.load_cell(workload)
    cfg = arch_config(cell)
    one = SingleDeviceSharding(device)
    mix = cell.mix
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
    steps = ServeSteps(cfg, ServeConfig(max_len=int(mix["max_len"])))
    params = param_structs(cfg, one, resident_weights(cell.raw))

    def cache(rows):
        s = jax.ShapeDtypeStruct((L, rows, int(mix["max_len"]), KV, hd),
                                 jnp.bfloat16, sharding=one)
        return {"k": s, "v": s}

    def ints(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    slots, chunk = int(mix["slots"]), int(mix["prefill_chunk"])
    x = jax.ShapeDtypeStruct((slots, 1, cfg.d_model), jnp.bfloat16,
                             sharding=one)
    programs = {
        f"prefill_chunk (1, {chunk})": steps.prefill_chunk_fn.lower(
            params, ints((1, chunk)), cache(1), ints((1,))),
        f"decode ({slots}, 1)": steps.decode_fn.lower(
            params, ints((slots, 1)), cache(slots), ints((slots,))),
        "head" + (" (tied)" if cfg.tie_embeddings else ""): jax.jit(
            lambda p, h: dense.logits_fn(cfg, p, h)).lower(params, x),
    }
    for what, lowered in programs.items():
        mem = lowered.compile().memory_analysis()
        print(f"{workload} {what}: {mem}", flush=True)


def main(argv=None) -> int:
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = argv or [w["name"] for w in json.loads(
        (BENCH.parent / "BENCHMARK.json").read_text())["workloads"]]
    for name in names:
        rehearse(name, topo.devices[0])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
