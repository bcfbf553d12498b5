"""A sparse-expert configuration given as new files only is served by the
harness through the launcher's normal ``compress`` and ``load`` and the
program's ``moe`` family, on the CPU at a tiny size: every request ends at
its length, and the window's work is counted by the family's own counts."""
import copy
import types

import numpy as np

import cell as cells
import sparse_expert_files as moe_files
from conftest import TINY_MIX


def test_served_by_the_harness(tmp_path):
    from repro.configs.base import MoEConfig
    from repro.models import api
    from serve_cell import ServedCell, arch_config
    moe_files.copy_bench(tmp_path)
    moe_files.add_sparse_expert_config(tmp_path)
    c = cells.load_cell(moe_files.WORKLOAD, root=tmp_path)
    c.mix = copy.deepcopy(TINY_MIX)
    shapes = api.param_shapes(arch_config(c))

    def make_weights(d, seed):
        """Seeded weights over the program's own parameters: norms 1,
        everything else normal with std 0.05."""
        rng = np.random.default_rng(seed)
        return {name: np.ones(shape, np.float32) if "norm" in name else
                (0.05 * rng.standard_normal(shape)).astype(np.float32)
                for name, shape in shapes.items()}

    sc = ServedCell(c, 7, types.SimpleNamespace(make_weights=make_weights))
    sc.build()
    assert sc.cfg.family == "moe"
    assert sc.cfg.moe == MoEConfig(num_experts=8, top_k=2, shared_experts=2)
    assert {"init_s", "compress_s", "load_s"} <= set(sc.setup)
    assert {"layers/moe/w_gate", "layers/moe/w_up", "layers/moe/w_down",
            "layers/moe/router"} <= set(sc.stored)
    sc.start()
    sc.ramp()
    ph = sc.measure(1.0)
    sc.release()
    lengths = set(sc.loop.outputs.tolist())
    assert ph.done and ph.failed == 0
    assert all(len(out) in lengths for _, out in ph.done)
    assert ph.tokens > 0 and ph.decode_steps > 0
    assert ph.flops > 0 and ph.decode_bytes > 0
    assert {"matmul_flops", "decode_weight_bytes"} <= set(c.adapter.CALLS)
